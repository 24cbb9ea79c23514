"""Engine dispatch-pipeline throughput (fig12-style, mixed lengths).

The host-side scheduling wins the paper attributes to RAPIDx's dispatcher
(§IV-B, Fig. 6): the wavefront runs exactly n + m trips per pair, never
the padded geometry. This benchmark builds a ragged mixed-length batch
whose true lengths are at most *half* the bucket geometry — alternating
(long read, short window) / (short read, long window) pairs, so the
group's padded bucket is long x long while every true n + m stays near
long + short — and measures `AlignmentEngine.align` wall time with
wavefront trimming on vs off.

Rows (per backend; the pallas rows emit only with a TPU attached — the
same t_max trims the kernel's step-chunk grid, but the 1024-geometry
sweep is infeasible in interpret mode on CPU):

  engine/mixed_trimmed      trimmed sweep (t_max = max true n + m)
  engine/mixed_untrimmed    full padded q_len + r_len sweep
  engine/tb_fetch_decode    packed traceback plane: bytes fetched per
                            pair per dispatch (2 flags/byte, DESIGN.md
                            §5) + batched nibble-decode wall time —
                            the decode="host" fallback path
  engine/tb_device_decode   on-device lockstep walk of the same planes
                            (core.traceback_device): RLE bytes actually
                            fetched per pair (trimmed to the longest
                            CIGAR) + decode/fetch/join wall time
  engine/ragged_tb_pipeline multi-class ragged request with CIGAR decode
                            through the async enqueue/finalize pipeline
  engine/xdrop_reject       seeded 70%-bad-pair candidate mix through
                            engines with xdrop=100 vs xdrop=None: the
                            X-drop rule retires every bad pair a small
                            fraction into its sweep and the backend
                            skips the remaining step chunks (DESIGN.md
                            §12); derived records speedup_vs_noxdrop
                            (CI-gated) and rejected_frac, and survivor
                            scores are asserted bit-identical first

The trimmed row's `derived` records speedup_vs_untrimmed, the
tb_fetch_decode row's records tb_bytes_per_pair / pack_ratio, and the
tb_device_decode row's records rle_bytes_per_pair /
fetch_cut_vs_packed_plane — the perf trajectory numbers captured in
BENCH_engine.json (acceptance: trimming >= 2x; pack_ratio ~= 2; RLE
fetch <= 1/10 of the packed-plane fetch).
"""

from __future__ import annotations

import sys

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_host_fn, time_host_paired
from repro.core import MINIMAP2, AlignmentEngine
from repro.core.banded import traceback_banded_batch
from repro.core.batch import AlignmentBatch, plan_buckets
from repro.core.traceback_device import (decode_packed_tb, fetch_rle,
                                         rle_to_cigars)

#: Long/short true lengths. The long side sits just above the 512 bucket
#: edge, so the group's padded geometry is 1024/1024 (T_full = 2048)
#: while every true n + m <= 552 (t_max = 576) — the wavefront-trimming
#: win the paper's exact-trip-count scheduling buys (§VI-F).
LONG, SHORT = 520, 32


def _mixed_halflength_pairs(n_pairs: int, seed: int = 61):
    """Alternating (long, short) / (short, long) encoded pairs: the
    bucket class is set by each pair's longest side, so the whole batch
    shares one long x long group whose true sweeps are all ~half the
    padded geometry."""
    rng = np.random.default_rng(seed)
    reads, refs = [], []
    for k in range(n_pairs):
        a, b = (LONG, SHORT) if k % 2 == 0 else (SHORT, LONG)
        read = rng.integers(0, 4, a).astype(np.int8)
        ref = rng.integers(0, 4, b).astype(np.int8)
        # Make the short side a mutated slice of the long one so the DP
        # has a real alignment to chase.
        src, dst = (read, ref) if a >= b else (ref, read)
        dst[:] = src[: len(dst)]
        mut = rng.integers(0, len(dst), max(len(dst) // 20, 1))
        dst[mut] = (dst[mut] + 1) % 4
        reads.append(read)
        refs.append(ref)
    return reads, refs


#: engine/xdrop_reject workload shape: the share of junk candidate pairs
#: (random vs random — a seeding stage's false positives) and the true
#: lengths of the two populations. Bad pairs are LONG_BAD so they land in
#: their own all-bad length class (1024 geometry) and dominate compute —
#: the regime where retiring them pays; good pairs are short mutated
#: copies that must come back bit-identical.
BAD_FRAC, GOOD_L, BAD_L = 0.7, 200, 600

#: Dispatch-slice capacity for the xdrop row. Lockstep batches sweep at
#: their slowest member's pace, and the retire-step distribution of
#: random pairs is heavy-tailed (most retire ~150 steps in; a rare
#: straggler tracks within xdrop of its best for most of the sweep) —
#: smaller slices localise a straggler to its own slice instead of
#: holding the whole class live.
XDROP_CAPACITY = 16


def _xdrop_mix(n_pairs: int, seed: int = 71):
    """Seeded candidate mix: (reads, refs, good_mask)."""
    rng = np.random.default_rng(seed)
    reads, refs, good = [], [], []
    n_bad = int(round(n_pairs * BAD_FRAC))
    for k in range(n_pairs):
        if k < n_pairs - n_bad:
            read = rng.integers(0, 4, GOOD_L).astype(np.int8)
            ref = read.copy()
            mut = rng.integers(0, GOOD_L, max(GOOD_L // 20, 1))
            ref[mut] = (ref[mut] + 1) % 4
            good.append(True)
        else:
            read = rng.integers(0, 4, BAD_L).astype(np.int8)
            ref = rng.integers(0, 4, BAD_L).astype(np.int8)
            good.append(False)
        reads.append(read)
        refs.append(ref)
    return reads, refs, np.asarray(good)


def _ragged_request(n_pairs: int, seed: int = 67):
    rng = np.random.default_rng(seed)
    lengths = (90, 250, 600)
    reads, refs = [], []
    for k in range(n_pairs):
        L = lengths[k % len(lengths)]
        read = rng.integers(0, 4, L).astype(np.int8)
        ref = read.copy()
        mut = rng.integers(0, L, max(L // 25, 1))
        ref[mut] = (ref[mut] + 1) % 4
        reads.append(read)
        refs.append(ref)
    return reads, refs


def run(backends=("reference", "pallas"), smoke=False):
    n_pairs = 8 if smoke else 64
    iters = 1 if smoke else 5
    reads, refs = _mixed_halflength_pairs(n_pairs)
    g = plan_buckets([len(x) for x in reads], [len(x) for x in refs])[0]
    T_full = g.spec.q_len + g.spec.r_len
    for backend in backends:
        if backend == "pallas":
            # The 1024x1024 bucket is the whole point of this benchmark
            # and is hours-long in interpret mode — kernel rows only make
            # sense compiled (TPU attached).
            from repro.kernels.banded_dp.banded_dp import default_interpret
            if default_interpret():
                # A note, not an emit(): a 0.0-us row would pollute the
                # machine-readable perf trajectory.
                print("engine: pallas rows skipped (interpret mode, "
                      "no TPU)", file=sys.stderr)
                continue
        # w=64 (the long-read accuracy regime of Table V) keeps per-step
        # band compute dominant over fixed dispatch overhead, so the
        # wall-time ratio tracks the step-count ratio.
        eng_t = AlignmentEngine(backend=backend, sc=MINIMAP2,
                                capacity=n_pairs, trim=True,
                                base_bandwidth=64)
        eng_u = AlignmentEngine(backend=backend, sc=MINIMAP2,
                                capacity=n_pairs, trim=False,
                                base_bandwidth=64)
        us_t, us_u = time_host_paired(lambda: eng_t.align(reads, refs),
                                      lambda: eng_u.align(reads, refs),
                                      iters)
        speedup = us_u / us_t
        emit("engine/mixed_trimmed", us_t / n_pairs,
             f"speedup_vs_untrimmed={speedup:.2f};t_max={g.spec.t_max};"
             f"T_full={T_full};n_pairs={n_pairs}", backend=backend)
        emit("engine/mixed_untrimmed", us_u / n_pairs,
             f"T_full={T_full};n_pairs={n_pairs}", backend=backend)

        # Packed traceback plane: the tb bytes one dispatch group
        # actually fetches to the host (2 flags per byte — half the
        # one-flag-per-byte layout's N x T x B) and the wall time of the
        # batched nibble decode over that packed plane.
        batch = AlignmentBatch.from_lists(reads, refs, capacity=n_pairs)
        spec = batch.spec
        out = eng_t.align_arrays(
            jnp.asarray(batch.q_pad), jnp.asarray(batch.r_pad),
            jnp.asarray(batch.n), jnp.asarray(batch.m), band=spec.band,
            collect_tb=True, t_max=spec.t_max)
        tb, los = np.asarray(out["tb"]), np.asarray(out["los"])
        unpacked_bytes = tb.shape[0] * tb.shape[1] * spec.band
        us_d = time_host_fn(traceback_banded_batch, tb, los,
                            batch.n, batch.m, spec.band, iters=iters)
        emit("engine/tb_fetch_decode", us_d / n_pairs,
             f"tb_bytes_per_pair={tb.nbytes // tb.shape[0]};"
             f"unpacked_bytes_per_pair={unpacked_bytes // tb.shape[0]};"
             f"pack_ratio={unpacked_bytes / tb.nbytes:.2f};"
             f"band={spec.band};t_max={spec.t_max}", backend=backend)

        # On-device decode of the very same planes: the host fetches only
        # the RLE CIGAR arrays trimmed to the longest path present —
        # O(path segments) bytes per pair instead of the packed plane.
        tb_dev, los_dev = out["tb"], out["los"]
        n_dev = jnp.asarray(batch.n, jnp.int32)
        m_dev = jnp.asarray(batch.m, jnp.int32)

        def dev_decode():
            ops, runs, lens = decode_packed_tb(tb_dev, los_dev, n_dev,
                                               m_dev, band=spec.band)
            fetched = fetch_rle({"cig_ops": ops, "cig_runs": runs,
                                 "cig_len": lens})
            return fetched, rle_to_cigars(*fetched)

        us_dd = time_host_fn(dev_decode, iters=iters)
        (ops_np, runs_np, lens_np), _ = dev_decode()
        rle_bytes = ops_np.nbytes + runs_np.nbytes + lens_np.nbytes
        tb_per_pair = tb.nbytes // tb.shape[0]
        rle_per_pair = max(rle_bytes // tb.shape[0], 1)
        emit("engine/tb_device_decode", us_dd / n_pairs,
             f"rle_bytes_per_pair={rle_per_pair};"
             f"tb_bytes_per_pair={tb_per_pair};"
             f"fetch_cut_vs_packed_plane={tb_per_pair / rle_per_pair:.1f};"
             f"k_used={ops_np.shape[1]};band={spec.band};"
             f"t_max={spec.t_max}", backend=backend)

        # Multi-class ragged request through the async enqueue/finalize
        # pipeline, CIGAR decode included (the serving-shaped number),
        # measured A/B-interleaved against the same request through the
        # persistent megakernel dispatch (ONE device program for all
        # groups, single trimmed RLE fetch — DESIGN.md §10).
        rreads, rrefs = _ragged_request(n_pairs)
        eng_p = AlignmentEngine(backend=backend, sc=MINIMAP2,
                                capacity=n_pairs, trim=True,
                                base_bandwidth=64, dispatch="persistent")
        us_p, us_pp = time_host_paired(
            lambda: eng_t.align(rreads, rrefs, collect_tb=True),
            lambda: eng_p.align(rreads, rrefs, collect_tb=True), iters)
        groups = eng_t.plan([len(x) for x in rreads],
                            [len(x) for x in rrefs])
        n_groups = len(groups)
        emit("engine/ragged_tb_pipeline", us_p / n_pairs,
             f"reads_per_s={n_pairs / (us_p / 1e6):.4g};"
             f"groups={n_groups};n_pairs={n_pairs}", backend=backend)

        # Roofline bound for the persistent request: per-group
        # compute/memory overlap bound + ONE dispatch overhead charge
        # (vs one per group pipelined) — the gap is the headroom the
        # device-side loop leaves on this host.
        from repro.roofline.analytic import (DISPATCH_OVERHEAD_S,
                                             alignment_roofline)
        bound_s = DISPATCH_OVERHEAD_S
        for g in groups:
            lens_g = [(len(rreads[i]) + len(rrefs[i])) / 2
                      for i in g.indices]
            a = alignment_roofline({
                "length": sum(lens_g) / len(lens_g), "band": g.spec.band,
                "global_batch": len(g.indices), "shape": "ragged",
                "mesh_shape": [1], "dispatch": "persistent"})
            bound_s += a["step_time_overlap_s"]
        bound_us = bound_s * 1e6
        emit("engine/persistent_dispatch", us_pp / n_pairs,
             f"speedup_vs_pipelined={us_p / us_pp:.2f};"
             f"roofline_bound_us={bound_us / n_pairs:.2f};"
             f"roofline_gap={us_pp / bound_us:.1f};"
             f"groups={n_groups};n_pairs={n_pairs};dispatch=persistent",
             backend=backend)

        # X-drop early termination on a seeded bad-candidate mix: the
        # 70% junk pairs sit alone in the long length class, retire ~1/8
        # into their sweep, and the backend skips their remaining step
        # chunks. Survivors are asserted bit-identical before timing.
        xdrop = 100
        xreads, xrefs, xgood = _xdrop_mix(n_pairs)
        eng_nx = AlignmentEngine(backend=backend, sc=MINIMAP2,
                                 capacity=XDROP_CAPACITY, trim=True)
        eng_x = AlignmentEngine(backend=backend, sc=MINIMAP2,
                                capacity=XDROP_CAPACITY, trim=True,
                                xdrop=xdrop)
        o_nx = eng_nx.align(xreads, xrefs)
        o_x = eng_x.align(xreads, xrefs)
        surv = o_x["status"] == 0
        assert np.all(o_x["status"][xgood] == 0), "a good pair was retired"
        for k in ("score", "best_score", "best_i", "best_j"):
            assert np.array_equal(o_nx[k][surv], o_x[k][surv]), \
                f"xdrop changed a survivor's {k}"
        us_x, us_nx = time_host_paired(
            lambda: eng_x.align(xreads, xrefs),
            lambda: eng_nx.align(xreads, xrefs), iters)
        rejected_frac = float((~surv).sum()) / n_pairs
        emit("engine/xdrop_reject", us_x / n_pairs,
             f"speedup_vs_noxdrop={us_nx / us_x:.2f};"
             f"rejected_frac={rejected_frac:.2f};xdrop={xdrop};"
             f"bad_frac={BAD_FRAC};n_pairs={n_pairs}", backend=backend)
