"""AlignmentEngine — the unified multi-backend alignment execution stack.

This is the host dispatcher of the paper's deployment picture (Fig. 2a):
requests arrive as ragged lists of (read, candidate window) pairs; the
engine

  1. plans per-length-class `DispatchGroup`s (`core.batch.plan_buckets`)
     so every compute dispatch runs a fixed geometry with its own adaptive
     band width B = min(w + 0.01 L, 100) — the paper's host-side length
     grouping that keeps each fixed-geometry compute memory full (§IV-B,
     Fig. 6). Each group also records its trimmed sweep length
     `t_max` (max true n + m, §VI-F) so no backend sweeps the dead
     diagonals of the padded geometry,
  2. dispatches groups through a depth-1 lookahead pipeline on the
     selected backend ('reference' = vmapped lax.scan, 'pallas' = the
     in-VMEM wavefront kernel, 'auto' = pallas on TPU else reference;
     see `core.backends`): group k+1's capacity slices are enqueued
     on-device before group k is materialised, so JAX async dispatch
     keeps the device computing group k+1 while the host fetches and
     CIGAR-decodes group k — with at most two groups' buffers live,
  3. with `mesh=`, shards each dispatch slice over the mesh's data axes
     via `shard_map` (paper Fig. 6(a) tile level: alignment needs no
     inter-tile communication, so the lowered program has zero
     collectives) — one capacity block per shard per slice,
  4. scatters results back into the caller's original read order, and
  5. when tracebacks are requested, walks every group's packed
     (T, ceil(B/2)) flag plane **on-device** with the jit'd lockstep
     decoder (`core.traceback_device`, fused onto the dispatch program)
     and fetches only fixed-width RLE CIGAR arrays trimmed to the
     longest path present — O(path segments) host bytes per pair instead
     of the ceil(B/2) x t_max plane (DESIGN.md §5). decode="host" keeps
     the vectorised numpy `traceback_banded_batch` path as the oracle
     and CPU fallback.

All backends return bit-identical results (integer DP) — the engine is a
pure scheduling layer. Layering and the backend contract are documented
in DESIGN.md. `engine.align` is the one-shot entry point; the streaming
front-end that keeps this pipeline continuously fed from a live request
stream is `repro.serve.AlignmentService`, which drives the same
`plan` / `enqueue_group` / `finalize_group` primitives (DESIGN.md §8).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import pathlib

import numpy as np

from repro import obs
from repro.core.backends import available_backends, get_backend, \
    resolve_backend
from repro.core.banded import validate_narrow_cells
from repro.core.batch import (DEFAULT_BAND_CAP, DEFAULT_BUCKET_EDGES,
                              BucketSpec, default_base_bandwidth,
                              enqueue_dispatch, finalize_dispatch, pad_group,
                              plan_buckets, run_dispatch)
from repro.core.scoring import ScoringConfig, MINIMAP2, adaptive_bandwidth

#: Result keys every backend returns for each pair (original read order).
#: 'status' is the xdrop early-termination verdict: 0 = aligned, k > 0 =
#: retired at wavefront step k (always 0 when xdrop is off).
SCALAR_KEYS = ("score", "final_lo", "best_score", "best_i", "best_j",
               "status")

#: Dummy-row pad multiple for persistent dispatch groups. The pipelined
#: path pads every group to its capacity slice (64 x num_shards) because
#: each slice is a separate launch; the persistent megakernel has no
#: per-group launch to amortise, so groups only pad to the kernel batch
#: tile — a ragged tail group of 22 pairs costs 24 slots, not 64.
PERSISTENT_PAD = 8


@dataclasses.dataclass
class PendingDispatch:
    """One enqueued (device-resident, not yet fetched) dispatch group.

    Produced by `AlignmentEngine.enqueue_group` and consumed by
    `AlignmentEngine.finalize_group`. Between the two calls the group's
    result buffers live only on the device (JAX async dispatch), so a
    caller holding several PendingDispatch handles is exactly the
    engine's lookahead pipeline — `engine.align` keeps one in flight
    (depth 1); the streaming `serve.AlignmentService` keeps up to its
    `max_inflight_groups`.
    """
    spec: BucketSpec
    n: np.ndarray        # (N_pad,) true query lengths incl. dummy pairs
    m: np.ndarray        # (N_pad,) true reference lengths
    outs: list           # raw per-slice backend result dicts (device)
    num_real: int        # request pairs before dummy padding
    collect_tb: bool
    mode: str

    @property
    def num_slots(self) -> int:
        """Padded dispatch slots (N_pad) — the fill-ratio denominator."""
        return int(self.n.shape[0])

    @property
    def signature(self) -> tuple:
        """The dispatch signature this group compiled under — one XLA
        program per distinct value (the key a depth autotuner or a
        warmup pass works in)."""
        return (self.spec.q_len, self.spec.r_len, self.spec.band,
                self.spec.t_max, self.mode, self.collect_tb)


@dataclasses.dataclass
class PendingPersistent:
    """One enqueued persistent-dispatch request (ALL of its groups in a
    single device program; see `AlignmentEngine.enqueue_persistent`).

    The same two-phase contract as `PendingDispatch`, at request
    granularity: between enqueue and finalize the merged result buffers
    live on the device, and `finalize_persistent` is the single host
    sync (the trimmed RLE fetch + scalar fetch)."""
    groups: list         # planned DispatchGroups (caller-order indices)
    batch: list          # per-group (q_pad, r_pad, n, m, band, t_max)
    outs: dict           # run_persistent's merged device result
    num_real: int        # request pairs before dummy padding
    collect_tb: bool
    mode: str

    @property
    def num_slots(self) -> int:
        """Padded rows across all groups — the fill-ratio denominator."""
        return sum(int(grp[0].shape[0]) for grp in self.batch)

    @property
    def signature(self) -> tuple:
        """The persistent program's compile key: the stacked group
        geometry (see PallasBackend.run_persistent's cache)."""
        return ("persistent",) + tuple(
            (int(grp[0].shape[0]), int(grp[0].shape[1]),
             int(grp[1].shape[1]), int(grp[4]), grp[5])
            for grp in self.batch)


#: The compile cache's home when JAX_COMPILATION_CACHE_DIR is unset: one
#: fixed directory inside the checkout (git-ignored). The path is part of
#: the cache key, so a directory that moved between runs would never hit.
CHECKOUT_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3]
                         / ".jax_cache")


def enable_compilation_cache(cache_dir: str | None = None) -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, wins: JAX already reads it, and
    no other directory is set here. Otherwise the cache goes to
    `cache_dir`, or to `CHECKOUT_CACHE_DIR` when that is None. Every
    dispatch-signature program is made eligible (the default thresholds
    skip sub-second compiles — exactly the many small per-signature
    programs a serving replica pays at traffic time), and the process's
    cache handle is re-initialised, since the first compile may have
    happened before this call with caching still off.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        cache_dir = env_dir
    else:
        cache_dir = cache_dir or CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    return cache_dir


def _check_t_max(t_max, n, m) -> None:
    """Reject a trimmed sweep shorter than some pair's true n + m — the
    carry would freeze before that pair's corner and silently return a
    truncated alignment. Only checkable where lengths are concrete; under
    jit/shard_map tracing the caller's guarantee stands."""
    if t_max is None:
        return
    import jax

    if isinstance(n, jax.core.Tracer) or isinstance(m, jax.core.Tracer):
        return
    lens = np.asarray(n).astype(np.int64) + np.asarray(m).astype(np.int64)
    if lens.size == 0:
        return
    t_true = int(lens.max())
    if t_max < t_true:
        raise ValueError(
            f"t_max={t_max} < max true n + m = {t_true}: the trimmed "
            "sweep would stop before every pair reaches its corner")


@dataclasses.dataclass
class AlignmentEngine:
    """One result contract over interchangeable execution backends.

    Attributes:
      backend: 'reference' | 'pallas' | 'auto' (resolved at construction),
        or an already-constructed backend object.
      sc: affine-gap scoring config shared by every dispatch.
      adaptive: adaptive wavefront direction (Table V ablation switch).
      base_bandwidth: w in B = min(w + 0.01 L, band_cap); None =
        per-class default (10 short / 30 long, §VI-B).
      band_cap: cap of the adaptive band width (paper §IV-B1; default
        100 per BWA-MEM's evidence). Raise it for long-read scenarios
        that need a wider band than the short-read default.
      capacity: pairs per dispatch group slice (sequence-level k). With a
        mesh this is the *per-shard* capacity: each dispatch slice spans
        capacity x num_shards pairs.
      backend_opts: forwarded to the backend constructor (e.g. batch_tile,
        chunk, interpret for pallas).
      trim: sweep each group only t_max wavefront steps (max true n + m
        of its members) instead of the full padded q_len + r_len.
        Results are bit-identical either way; False exists for the
        trimming-parity tests and benchmarks.
      dispatch: "pipelined" (default) or "persistent". Pipelined is the
        depth-1 lookahead loop: one backend launch per dispatch group
        slice, host mediating group boundaries. Persistent hands ALL of
        a request's groups to the backend's `run_persistent` in ONE
        device program (DESIGN.md §10): per-group t_max/band become
        device-side loop bounds, the RLE decode is fused behind the
        compute, groups pad only to `PERSISTENT_PAD` instead of the
        capacity slice, and the single host sync is the trimmed RLE
        fetch at the end. Results are bit-identical (asserted by
        tests/test_persistent_dispatch.py). Persistent requires
        mesh=None and (with collect_tb) decode="device".
      cell_dtype: "int32" (default) or "narrow" — backend band-state
        storage precision (paper §IV bit-width reduction). Narrow keeps
        int8 difference planes + int16 band-relative H; bit-exact with
        int32 under the static guard `validate_narrow_cells(sc,
        band_cap)`, which runs at construction and rejects scoring
        configs whose worst case could overflow.
      xdrop: X-drop early-termination threshold (None = off). When set,
        a pair retires the first wavefront step its live-band max H
        falls more than `xdrop` below the pair's running best; its
        'status' reports the retiring step (0 = aligned), its 'score'
        stays at the NEG sentinel and its CIGAR entry is None. Surviving
        pairs are bit-identical to an xdrop-off run (the retire freeze
        is the same carry freeze the trimmed sweep uses); backends skip
        the remaining step chunks of fully-retired batches, which is
        where the wall-clock saving comes from (DESIGN.md §12).
      decode: traceback decode stage for the ragged `align` path.
        "device" (default) fuses the lockstep walker after the compute —
        the packed tb plane never leaves the device and the host fetches
        RLE CIGAR arrays; "host" fetches the packed plane and decodes
        with the numpy `traceback_banded_batch` (oracle / CPU fallback).
        CIGARs are bit-identical either way.
      mesh: optional jax.sharding.Mesh — shard every dispatch slice's
        batch dimension over `batch_axes` with shard_map (tile-level
        parallelism, Fig. 6(a)).
      batch_axes: mesh axes to shard over; None = every axis named
        "pod"/"data" in the mesh (alignment never uses "model").
      compilation_cache_dir: when set, wire JAX's persistent
        compilation cache to this directory through
        `enable_compilation_cache`, which yields to
        JAX_COMPILATION_CACHE_DIR when that is set. A replica restarted
        against a warm cache deserialises its dispatch signatures
        instead of recompiling them — pair with `warmup()` so the
        deserialisation happens before traffic arrives. The flag is
        process-global in JAX; constructing two engines with different
        directories moves the cache for both.
    """

    backend: object = "auto"
    sc: ScoringConfig = MINIMAP2
    adaptive: bool = True
    base_bandwidth: int | None = None
    band_cap: int = DEFAULT_BAND_CAP
    capacity: int = 64
    backend_opts: dict | None = None
    bucket_edges: tuple = DEFAULT_BUCKET_EDGES
    trim: bool = True
    dispatch: str = "pipelined"
    cell_dtype: str = "int32"
    xdrop: int | None = None
    decode: str = "device"
    mesh: object = None
    batch_axes: tuple | None = None
    compilation_cache_dir: str | None = None

    def __post_init__(self):
        obs.install()
        if self.compilation_cache_dir is not None:
            enable_compilation_cache(self.compilation_cache_dir)
        self.backend = get_backend(self.backend,
                                   **(self.backend_opts or {}))
        if self.dispatch not in ("pipelined", "persistent"):
            raise ValueError(f"dispatch must be 'pipelined' or "
                             f"'persistent', got {self.dispatch!r}")
        if self.cell_dtype not in ("int32", "narrow"):
            raise ValueError(f"cell_dtype must be 'int32' or 'narrow', "
                             f"got {self.cell_dtype!r}")
        if self.xdrop is not None and int(self.xdrop) <= 0:
            raise ValueError(f"xdrop must be a positive threshold or "
                             f"None, got {self.xdrop!r}")
        if self.cell_dtype == "narrow":
            # Static overflow guard: the band never exceeds band_cap, and
            # the bound is monotonic in the band width, so checking the
            # cap covers every dispatch this engine can plan.
            validate_narrow_cells(self.sc, self.band_cap)
        if self.dispatch == "persistent" and self.mesh is not None:
            raise ValueError(
                "dispatch='persistent' runs the whole request as one "
                "single-device program and cannot shard over a mesh; use "
                "the pipelined dispatch with mesh=")
        if self.mesh is not None and self.batch_axes is None:
            self.batch_axes = tuple(a for a in self.mesh.axis_names
                                    if a in ("pod", "data"))
        self._runners: dict = {}

    @property
    def backend_name(self) -> str:
        return self.backend.name

    @property
    def num_shards(self) -> int:
        """Mesh shards a dispatch slice spans (1 without a mesh)."""
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes],
                           dtype=np.int64))

    # ------------------------------------------------------------------
    # Mesh path: one jit'd shard_map program per dispatch signature.
    # ------------------------------------------------------------------
    def sharded_runner(self, *, band: int, collect_tb: bool = False,
                       mode: str = "global", t_max: int | None = None,
                       decode: str = "host"):
        """The jit'd shard_map'd backend program for one dispatch
        signature (cached per engine). The batch dimension of every
        argument shards over the mesh's `batch_axes`; because the
        backend contract is jax-traceable and alignment is
        embarrassingly parallel, the lowered program contains zero
        collectives (asserted by tests/test_distributed.py) — including
        with decode="device", where the lockstep traceback walker is
        fused under the same shard_map (the walk is per-pair, so it
        shards with the batch and needs no communication either)."""
        if self.mesh is None:
            raise ValueError("sharded_runner requires AlignmentEngine("
                             "mesh=...)")
        key = (band, collect_tb, mode, t_max, decode, self.xdrop)
        fn = self._runners.get(key)
        if fn is None:
            import jax
            from jax.sharding import PartitionSpec as P
            from repro.core.distributed import shard_map

            spec = P(self.batch_axes)

            def local_align(q, r, n, m):
                return self.backend.run(q, r, n, m, sc=self.sc, band=band,
                                        adaptive=self.adaptive,
                                        collect_tb=collect_tb, mode=mode,
                                        t_max=t_max, decode=decode,
                                        cell_dtype=self.cell_dtype,
                                        xdrop=self.xdrop)

            fn = jax.jit(shard_map(local_align, mesh=self.mesh,
                                   in_specs=(spec, spec, spec, spec),
                                   out_specs=spec))
            self._runners[key] = fn
        return fn

    # ------------------------------------------------------------------
    # Padded single-length-class path (jax arrays in, jax arrays out).
    # ------------------------------------------------------------------
    def align_arrays(self, q_pad, r_pad, n, m, *, band: int | None = None,
                    mode: str = "global", collect_tb: bool = False,
                    t_max: int | None = None, decode: str = "host"):
        """Align an already-padded single-class batch on the backend.

        The thin path used by `edit_distance_batch`, `core.distributed`
        and the benchmarks; returns the raw backend result dict. With
        `mesh=`, the batch shards over the mesh (its leading dimension
        must divide by `num_shards`). `t_max` optionally trims the sweep
        (caller guarantees t_max >= max true n + m). `decode` defaults to
        "host" here — the raw-plane contract (tb/los device arrays) that
        the oracle tests and plane-level tooling consume; pass "device"
        to get the fused on-device walk's RLE arrays instead.
        """
        if band is None:
            L = max(int(q_pad.shape[1]), int(r_pad.shape[1]))
            band = adaptive_bandwidth(L, default_base_bandwidth(
                L, self.base_bandwidth), cap=self.band_cap)
        _check_t_max(t_max, n, m)
        if self.mesh is not None:
            fn = self.sharded_runner(band=band, collect_tb=collect_tb,
                                     mode=mode, t_max=t_max, decode=decode)
            return fn(q_pad, r_pad, n, m)
        return self.backend.run(q_pad, r_pad, n, m, sc=self.sc, band=band,
                                adaptive=self.adaptive,
                                collect_tb=collect_tb, mode=mode,
                                t_max=t_max, decode=decode,
                                cell_dtype=self.cell_dtype,
                                xdrop=self.xdrop)

    # ------------------------------------------------------------------
    # Group-at-a-time pipeline primitives (the service's driving API).
    # ------------------------------------------------------------------
    def plan(self, q_lens, r_lens):
        """Plan per-length-class `DispatchGroup`s for a ragged request
        under this engine's bucketing config (edges, band_cap, capacity,
        base_bandwidth) — the scheduler `align` and the streaming
        `serve.AlignmentService` share."""
        return plan_buckets(q_lens, r_lens,
                            base_bandwidth=self.base_bandwidth,
                            capacity=self.capacity,
                            edges=self.bucket_edges,
                            band_cap=self.band_cap)

    def enqueue_group(self, reads, refs, spec: BucketSpec, *,
                      mode: str = "global",
                      collect_tb: bool = False) -> PendingDispatch:
        """Pad one length-class's member pairs and enqueue them on the
        device (async — no host sync). `reads`/`refs` are the group
        members in group order (the caller keeps the scatter indices).
        Returns the `PendingDispatch` handle for `finalize_group`."""
        t_max = spec.t_max if self.trim else None
        q_pad, r_pad, n, m = pad_group(
            reads, refs, spec, pad_multiple=spec.capacity * self.num_shards)
        if self.mesh is not None:
            run = self.sharded_runner(
                band=spec.band, collect_tb=collect_tb, mode=mode,
                t_max=t_max, decode=self.decode)
        else:
            run = functools.partial(
                self.backend.run, sc=self.sc, band=spec.band,
                adaptive=self.adaptive, collect_tb=collect_tb,
                mode=mode, t_max=t_max, decode=self.decode,
                cell_dtype=self.cell_dtype, xdrop=self.xdrop)
        outs = enqueue_dispatch(run, q_pad, r_pad, n, m,
                                capacity=spec.capacity * self.num_shards)
        return PendingDispatch(spec=spec, n=n, m=m, outs=outs,
                               num_real=len(reads), collect_tb=collect_tb,
                               mode=mode)

    def finalize_group(self, pending: PendingDispatch, *,
                       stats: dict | None = None) -> dict:
        """Materialise an enqueued group: blocks only on *that* group's
        device work, strips dummy padding, and (with collect_tb) joins
        its CIGARs per the engine's decode stage. With `stats`, reports
        the bytes this fetch really materialised
        (`stats["fetched_bytes"]`, padded rows included)."""
        return finalize_dispatch(pending.outs, pending.n, pending.m,
                                 band=pending.spec.band,
                                 num_real=pending.num_real,
                                 collect_tb=pending.collect_tb,
                                 mode=pending.mode, decode=self.decode,
                                 stats=stats)

    # ------------------------------------------------------------------
    # Persistent-dispatch pipeline primitives (request granularity).
    # ------------------------------------------------------------------
    def enqueue_persistent(self, reads, refs, *, mode: str = "global",
                           collect_tb: bool = False) -> PendingPersistent:
        """Plan a whole ragged request and enqueue ALL of its groups as
        ONE device program (`run_persistent`, DESIGN.md §10) — no host
        sync. The `PendingPersistent` handle goes to
        `finalize_persistent`; a caller interleaving several handles
        pipelines whole requests the way `enqueue_group` pipelines
        groups (the streaming service does exactly this when its engine
        runs `dispatch="persistent"`)."""
        if self.dispatch != "persistent":
            raise ValueError("enqueue_persistent requires AlignmentEngine("
                             "dispatch='persistent')")
        if collect_tb and self.decode != "device":
            raise ValueError(
                "dispatch='persistent' fuses the traceback decode "
                "on-device; decode='host' exists only on the pipelined "
                "path")
        if not len(reads):
            raise ValueError("enqueue_persistent needs at least one pair")
        groups = self.plan([len(x) for x in reads],
                           [len(x) for x in refs])
        batch = []
        for g in groups:
            idx = g.indices
            t_max = g.spec.t_max if self.trim else None
            q_pad, r_pad, n, m = pad_group(
                [reads[i] for i in idx], [refs[i] for i in idx], g.spec,
                pad_multiple=PERSISTENT_PAD)
            _check_t_max(t_max, n, m)
            batch.append((q_pad, r_pad, n, m, g.spec.band, t_max))
        outs = self.backend.run_persistent(
            batch, sc=self.sc, adaptive=self.adaptive,
            collect_tb=collect_tb, mode=mode, decode=self.decode,
            cell_dtype=self.cell_dtype, xdrop=self.xdrop)
        return PendingPersistent(groups=groups, batch=batch, outs=outs,
                                 num_real=len(reads),
                                 collect_tb=collect_tb, mode=mode)

    def finalize_persistent(self, pending: PendingPersistent, *,
                            stats: dict | None = None) -> dict:
        """Materialise a persistent request — the single host sync of
        the persistent dispatch path: fetch the scalars (and, with
        collect_tb, the trimmed RLE arrays), strip the per-group dummy
        padding, and scatter back to the caller's original pair order.
        Returns (N,) arrays for the SCALAR_KEYS plus 'band', and
        'cigars' when tracebacks were collected. With `stats`, reports
        `stats["fetched_bytes"]` (padded rows included)."""
        fetched = 0

        def fetch(x) -> np.ndarray:
            nonlocal fetched
            arr = np.asarray(x)
            fetched += arr.nbytes
            return arr

        N = pending.num_real
        out = {k: np.zeros(N, np.int32) for k in SCALAR_KEYS}
        out["band"] = np.zeros(N, np.int32)
        merged = pending.outs
        with obs.span("serve.fetch") as sp:
            if pending.collect_tb:
                from repro.core.traceback_device import rle_to_cigars
                lens = fetch(merged["cig_len"])
                k_used = max(int(lens.max(initial=0)), 1)
                ops = fetch(merged["cig_ops"][:, :k_used])
                runs = fetch(merged["cig_runs"][:, :k_used])
            scalars = {k: fetch(merged[k]) for k in SCALAR_KEYS}
            sp.set_metadata(bytes=fetched)
        cigars: list = [None] * N
        off = 0
        with obs.span("serve.decode", pairs=N):
            for g, grp in zip(pending.groups, pending.batch):
                idx = g.indices
                n_real = len(idx)
                for key in SCALAR_KEYS:
                    out[key][idx] = scalars[key][off:off + n_real]
                out["band"][idx] = g.spec.band
                if pending.collect_tb:
                    cigs = rle_to_cigars(ops[off:off + n_real],
                                         runs[off:off + n_real],
                                         lens[off:off + n_real])
                    st = scalars["status"][off:off + n_real]
                    for pos, cig, rej in zip(idx, cigs, st != 0):
                        cigars[pos] = None if rej else cig
                off += grp[0].shape[0]  # past this group's padded rows
        if pending.collect_tb:
            out["cigars"] = cigars
        if stats is not None:
            stats["fetched_bytes"] = fetched
        return out

    # ------------------------------------------------------------------
    # Compile warm-start.
    # ------------------------------------------------------------------
    def warmup(self, lengths, *, mode: str = "global",
               collect_tb: bool = False) -> int:
        """Pre-compile the dispatch programs for the signatures a
        replica will serve, so the first real request does not pay
        compile latency at traffic time.

        `lengths` is an iterable of representative (q_len, r_len) pairs
        — one per length class the replica expects, at that class's
        *maximum* true lengths (the trimmed sweep t_max, and therefore
        the compiled program, is keyed on the group maximum). The
        warmup runs one dummy alignment through the full dispatch path
        (plan -> enqueue -> finalize, or the persistent program), which
        both populates the in-process jit caches and — with
        `compilation_cache_dir` set — writes the persistent compilation
        cache a future replica deserialises from. Returns the number of
        dispatch groups warmed."""
        lengths = list(lengths)
        if not lengths:
            return 0
        reads = [np.zeros(int(q), np.int8) for q, _ in lengths]
        refs = [np.zeros(int(r), np.int8) for _, r in lengths]
        self.align(reads, refs, mode=mode, collect_tb=collect_tb)
        return len(self.plan([len(x) for x in reads],
                             [len(x) for x in refs]))

    # ------------------------------------------------------------------
    # Ragged multi-bucket path (lists in, original-order numpy out).
    # ------------------------------------------------------------------
    def align(self, reads, refs, *, mode: str = "global",
              collect_tb: bool = False):
        """Align ragged (read, reference) lists through the multi-bucket
        scheduler.

        The dispatch pipeline overlaps host and device with a depth-1
        lookahead: group k+1's capacity slices are enqueued on-device
        (async — no host sync) *before* group k is fetched and decoded,
        so the host CIGAR-decodes group k while the device computes
        group k+1, and at most two groups' result buffers are live at
        once (bounded memory at any request size).

        Returns a dict of (N,) arrays in the caller's original order:
        the SCALAR_KEYS plus 'band' (the per-read band width actually
        used); with collect_tb also 'cigars' (list of N CIGARs — by
        default walked on-device per group by the fused lockstep decoder
        and fetched as trimmed RLE arrays, with semiglobal start-cell
        selection on-device off the tracked best cell; decode="host"
        falls back to fetching the packed plane and running the numpy
        batched traceback. Identical CIGARs either way).
        """
        if len(reads) != len(refs):
            raise ValueError("reads and refs must pair up")
        if self.dispatch == "persistent":
            return self._align_persistent(reads, refs, mode=mode,
                                          collect_tb=collect_tb)
        N = len(reads)
        out = {k: np.zeros(N, np.int32) for k in SCALAR_KEYS}
        out["band"] = np.zeros(N, np.int32)
        cigars: list = [None] * N

        groups = self.plan([len(x) for x in reads],
                           [len(x) for x in refs])

        def enqueue(g):
            idx = g.indices
            pd = self.enqueue_group([reads[i] for i in idx],
                                    [refs[i] for i in idx], g.spec,
                                    mode=mode, collect_tb=collect_tb)
            return g, pd

        # Depth-1 lookahead pipeline: group k+1 is enqueued on-device
        # before group k is materialised, so decode overlaps compute
        # while only two groups' buffers are ever live.
        pending = enqueue(groups[0]) if groups else None
        for k in range(len(groups)):
            g, pd = pending
            pending = enqueue(groups[k + 1]) if k + 1 < len(groups) \
                else None
            idx = g.indices
            merged = self.finalize_group(pd)
            for key in SCALAR_KEYS:
                out[key][idx] = merged[key]
            out["band"][idx] = g.spec.band
            if collect_tb:
                for pos, cig in zip(idx, merged["cigars"]):
                    cigars[pos] = cig
        if collect_tb:
            out["cigars"] = cigars
        return out

    def _align_persistent(self, reads, refs, *, mode: str,
                          collect_tb: bool):
        """The persistent-dispatch realisation of `align`: every planned
        group goes to the backend's `run_persistent` in ONE device
        program — no per-group launches, no host mediation between
        groups, and (with collect_tb) exactly one host sync: the trimmed
        RLE fetch over the whole request. Groups pad to `PERSISTENT_PAD`
        rather than the capacity slice, so ragged tail groups stop
        paying for empty dispatch slots. Output contract is identical to
        the pipelined `align` (bit-exact, asserted by
        tests/test_persistent_dispatch.py)."""
        if not len(reads):
            out = {k: np.zeros(0, np.int32) for k in SCALAR_KEYS}
            out["band"] = np.zeros(0, np.int32)
            if collect_tb:
                out["cigars"] = []
            return out
        pending = self.enqueue_persistent(reads, refs, mode=mode,
                                          collect_tb=collect_tb)
        return self.finalize_persistent(pending)


__all__ = ["AlignmentEngine", "PendingDispatch", "PendingPersistent",
           "SCALAR_KEYS", "enable_compilation_cache", "available_backends", "get_backend",
           "resolve_backend", "run_dispatch"]
