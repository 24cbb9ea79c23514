"""On-device lockstep traceback decode (paper §V-C3, the peripheral walk).

RAPIDx never ships the flag planes across the memory interface: dedicated
peripheral logic *next to the arrays* walks the path and only the tiny
CIGAR stream leaves. This module is that peripheral logic on the
accelerator side of the JAX stack: a jit'd, vectorised walker that
consumes the packed ``(N, T, ceil(B/2))`` traceback plane and the ``los``
band offsets **while they are still device arrays** and emits fixed-width
run-length-encoded CIGARs. Only the RLE arrays —

    cig_ops   (N, K) uint8   op codes (1 = M, 2 = I, 3 = D; 0 = unused)
    cig_runs  (N, K) int32   run lengths
    cig_len   (N,)   int32   number of RLE segments per pair

with ``K = T`` (the trimmed sweep length bounds the path length, since
every traceback step consumes at least one wavefront step) — ever become
host-fetch candidates, and the engine additionally trims the fetch to the
longest CIGAR actually present, collapsing per-pair host traffic from
``ceil(B/2) * t_max`` plane bytes to ``O(path segments)``.

Lockstep structure mirrors the host oracle `banded.traceback_banded_batch`
exactly (same 4-bit flag semantics, same band-escape diagonal fallback,
same boundary forced-gap rules), with one mechanical difference: entering
a gap run and emitting its first op are fused into one step, so every
scan iteration emits exactly one op per still-active pair and the walk
needs at most ``T`` iterations. The emitted op stream — and therefore the
decoded CIGAR — is identical by construction, and asserted bit-identical
across backends x modes x band parities by tests/test_device_traceback.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.banded import _OP_CHARS, _OP_D, _OP_I, _OP_M, \
    select_tb_nibble


@functools.partial(jax.jit, static_argnames=("band",))
def decode_packed_tb(tb, los, start_i, start_j, *, band: int):
    """Walk every pair's packed flag plane on-device, in lockstep.

    Args:
      tb: (N, T, ceil(band/2)) uint8 packed flag planes (device array,
        `pack_tb_lanes` layout).
      los: (N, T+1) int32 band offsets.
      start_i, start_j: (N,) int32 traceback start cells — (n, m) for
        global mode, the tracked best cell for semiglobal/extension
        (paper §III-A2: "traceback starts from the max cell").
      band: band width B (static).

    Returns (cig_ops, cig_runs, cig_len) as device arrays — the
    fixed-width RLE CIGAR layout above, runs in path order (start of the
    alignment first, exactly like the host decoder's output).
    """
    tb = jnp.asarray(tb)
    los = jnp.asarray(los)
    N, T, _ = tb.shape
    idx = jnp.arange(N, dtype=jnp.int32)
    i0 = jnp.asarray(start_i, jnp.int32)
    j0 = jnp.asarray(start_j, jnp.int32)

    def lookup(ii, jj):
        """Flags at (ii, jj) per pair + in-band validity. One byte gather
        from the packed plane, then the shared nibble select."""
        t = ii + jj
        lo = jnp.take_along_axis(los, jnp.clip(t, 0, T)[:, None],
                                 axis=1)[:, 0]
        k = ii - lo
        ok = (t >= 1) & (k >= 0) & (k < band)
        kc = jnp.clip(k, 0, band - 1)
        byte = tb[idx, jnp.clip(t - 1, 0, T - 1), kc >> 1]
        return select_tb_nibble(byte.astype(jnp.int32), kc), ok

    def step(carry, _):
        i, j, st = carry
        active = (i > 0) | (j > 0)
        c, in_band = lookup(i, j)
        cu, up_ok = lookup(i - 1, j)
        cl, left_ok = lookup(i, j - 1)
        d = c & 3

        # Branch masks — the same case split as the host walker. Entering
        # a gap run (state 0, d != 0) is fused with emitting its first op.
        b_del = active & (i == 0)
        b_ins = active & (i > 0) & (j == 0)
        interior = active & (i > 0) & (j > 0)
        esc = interior & ~in_band          # band escape: diagonal fallback
        core = interior & in_band
        diag = core & (st == 0) & (d == 0)
        ins = core & ((st == 1) | ((st == 0) & (d == 1)))
        dele = core & ((st == 2) | ((st == 0) & (d >= 2)))

        # Gap-extend bits live on the *next* cell of the run (Eq. (4)
        # regrouping): E reads (i-1, j), F reads (i, j-1).
        ext_e = up_ok & (i - 1 >= 1) & (j >= 1) & ((cu & 4) != 0)
        ext_f = left_ok & (j - 1 >= 1) & (i >= 1) & ((cl & 8) != 0)

        emit = jnp.where(b_ins | ins, _OP_I,
                         jnp.where(b_del | dele, _OP_D,
                                   jnp.where(diag | esc, _OP_M, 0)))
        di = (diag | esc | b_ins | ins).astype(jnp.int32)
        dj = (diag | esc | b_del | dele).astype(jnp.int32)
        new_st = jnp.where(ins, jnp.where(ext_e, 1, 0),
                           jnp.where(dele, jnp.where(ext_f, 2, 0), st))
        return (i - di, j - dj, new_st.astype(jnp.int32)), \
            emit.astype(jnp.uint8)

    st0 = jnp.zeros((N,), jnp.int32)
    _, emitted = jax.lax.scan(step, (i0, j0, st0), None, length=T)
    emitted = emitted.T  # (N, T), walk order: end of the alignment first

    # ---- fixed-width RLE of the reversed (path-order) op stream ----
    # Every active iteration emits exactly one op, so pair p's stream is
    # the nonzero prefix emitted[p, :path_len].
    path_len = jnp.sum((emitted != 0).astype(jnp.int32), axis=1)
    s = jnp.arange(T, dtype=jnp.int32)[None, :]
    rev = path_len[:, None] - 1 - s
    valid = rev >= 0
    cig = jnp.take_along_axis(emitted, jnp.clip(rev, 0, T - 1), axis=1)
    cig = jnp.where(valid, cig, 0)
    prev = jnp.concatenate([jnp.zeros((N, 1), cig.dtype), cig[:, :-1]],
                           axis=1)
    newseg = valid & (cig != prev)
    seg = jnp.cumsum(newseg.astype(jnp.int32), axis=1) - 1
    segc = jnp.clip(seg, 0, T - 1)
    cig_len = jnp.sum(newseg.astype(jnp.int32), axis=1)
    cig_runs = jnp.zeros((N, T), jnp.int32).at[idx[:, None], segc].add(
        valid.astype(jnp.int32))
    cig_ops = jnp.zeros((N, T), jnp.uint8).at[idx[:, None], segc].max(
        jnp.where(valid, cig, 0))
    return cig_ops, cig_runs, cig_len


def device_decode_result(out: dict, n, m, *, band: int,
                         mode: str = "global") -> dict:
    """Fuse the decode stage onto a backend result: consume ``tb``/``los``
    (still device values — under jit/shard_map they are plain traced
    intermediates and never materialise) and return the result dict with
    the RLE CIGAR arrays in their place.

    Start-cell selection happens on-device: global mode walks from
    (n, m), semiglobal from the tracked best cell on the last read row —
    no host round-trip for ``best_i``/``best_j``.

    Pairs the xdrop rule retired ('status' != 0) never completed their
    sweep, so their tb plane past the retiring step is frozen-carry
    garbage: their start cell is zeroed, which makes the lockstep walk a
    no-op and their CIGAR empty (the engine maps it to None).
    """
    out = dict(out)
    tb = out.pop("tb")
    los = out.pop("los")
    if mode == "semiglobal":
        start_i, start_j = out["best_i"], out["best_j"]
    else:
        start_i = jnp.asarray(n, jnp.int32)
        start_j = jnp.asarray(m, jnp.int32)
    status = out.get("status")
    if status is not None:
        rejected = status != 0
        start_i = jnp.where(rejected, 0, start_i)
        start_j = jnp.where(rejected, 0, start_j)
    ops, runs, lens = decode_packed_tb(tb, los, start_i, start_j, band=band)
    out["cig_ops"] = ops
    out["cig_runs"] = runs
    out["cig_len"] = lens
    return out


def fetch_rle(out: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Materialise a device-decoded result's RLE arrays on the host,
    trimmed to the longest CIGAR actually present.

    Fetches ``cig_len`` first (N x 4 bytes), slices the op/run planes on
    the device to ``K_used = max(cig_len)`` columns, and only then copies
    them — so host traffic per pair is ``5 * K_used + 4`` bytes, O(path
    segments), never the static K = t_max bound.
    """
    with obs.span("serve.fetch") as sp:
        lens = np.asarray(out["cig_len"])
        k_used = max(int(lens.max(initial=0)), 1)
        ops = np.asarray(out["cig_ops"][:, :k_used])
        runs = np.asarray(out["cig_runs"][:, :k_used])
        sp.set_metadata(bytes=lens.nbytes + ops.nbytes + runs.nbytes)
    return ops, runs, lens


def rle_to_cigars(ops: np.ndarray, runs: np.ndarray,
                  lens: np.ndarray) -> list[list[tuple[str, int]]]:
    """Join host-fetched RLE arrays into the list-of-(op, run) CIGAR
    format shared with the host decoder. O(total segments) host work —
    the only per-pair loop left on the traceback path."""
    return [[(_OP_CHARS[int(o)], int(r))
             for o, r in zip(ops[p, :lens[p]], runs[p, :lens[p]])]
            for p in range(ops.shape[0])]
