"""jit'd public wrapper for the banded DP Pallas kernel.

Handles batch padding to the kernel tile, dispatch, and exposes the same
result dict as `core.banded.banded_align_batch` so callers can swap the
XLA reference path and the kernel path behind one API.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.scoring import ScoringConfig
from repro.kernels.banded_dp.banded_dp import banded_align_pallas


def banded_align_kernel_batch(q_pad, r_pad, n, m, *, sc: ScoringConfig,
                              band: int, adaptive: bool = True,
                              collect_tb: bool = True, mode: str = "global",
                              batch_tile: int = 8, chunk: int = 128,
                              interpret: bool | None = None,
                              t_max: int | None = None,
                              cell_dtype: str = "int32",
                              xdrop: int | None = None):
    """Kernel-path batched alignment.

    Pads the batch up to a multiple of batch_tile with dummy pairs, runs
    the Pallas wavefront, and strips the padding. Returns the same result
    dict as `core.banded.banded_align_batch`: always 'score', 'final_lo',
    'best_score', 'best_i', 'best_j', 'status' (each (N,) int32; status
    0 = aligned, k > 0 = xdrop-retired at step k); with collect_tb
    also 'tb' ((N, T, ceil(B/2)) uint8 — 4-bit flags packed two lanes per
    byte, `core.banded.pack_tb_lanes` layout) and 'los' ((N, T+1) int32),
    where T = t_max (the trimmed sweep length, >= max true n + m) or
    Lq + Lr.
    """
    q_pad = jnp.asarray(q_pad)
    r_pad = jnp.asarray(r_pad)
    n = jnp.asarray(n, jnp.int32)
    m = jnp.asarray(m, jnp.int32)
    N = q_pad.shape[0]
    N_pad = int(-(-N // batch_tile) * batch_tile)
    if N_pad != N:
        pad = N_pad - N
        q_pad = jnp.concatenate(
            [q_pad, jnp.full((pad, q_pad.shape[1]), 4, q_pad.dtype)])
        r_pad = jnp.concatenate(
            [r_pad, jnp.full((pad, r_pad.shape[1]), 4, r_pad.dtype)])
        n = jnp.concatenate([n, jnp.ones((pad,), jnp.int32)])
        m = jnp.concatenate([m, jnp.ones((pad,), jnp.int32)])

    out = banded_align_pallas(q_pad, r_pad, n, m, sc=sc, band=band,
                              adaptive=adaptive, collect_tb=collect_tb,
                              mode=mode, batch_tile=batch_tile,
                              chunk=chunk, interpret=interpret, t_max=t_max,
                              cell_dtype=cell_dtype, xdrop=xdrop)
    return {k: v[:N] for k, v in out.items()}
