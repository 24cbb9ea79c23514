"""Pallas backend: the in-VMEM wavefront kernel (kernels.banded_dp).

The TPU compute-memory analogue of the RAPIDx CM array. On CPU hosts the
kernel runs in interpret mode (bit-exact, for validation); on TPU it
compiles. `interpret=None` leaves the choice to the kernel's
`default_interpret` (compiled exactly when a TPU is attached).

Persistent dispatch (`run_persistent`) stacks every group of a request
into one uniform (G, nb_max, bt, L_max) layout and launches the
`kernels.banded_dp.persistent` megakernel ONCE over all of them — the
group table rides as scalar-prefetch operands and becomes the
device-side dispatch queue, per-group t_max/band honoured by masked
chunk loops and band-lane masking. The fused per-group RLE decodes and
the merge run in the same jit program, cached per request signature.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import numpy as np

from repro.kernels.banded_dp.ops import banded_align_kernel_batch


@dataclasses.dataclass(frozen=True)
class PallasBackend:
    name = "pallas"
    batch_tile: int = 8
    chunk: int = 128
    interpret: bool | None = None

    def run(self, q_pad, r_pad, n, m, *, sc, band, adaptive=True,
            collect_tb=True, mode="global", t_max=None, decode="host",
            cell_dtype="int32", xdrop=None):
        out = banded_align_kernel_batch(
            q_pad, r_pad, n, m, sc=sc, band=band, adaptive=adaptive,
            collect_tb=collect_tb, mode=mode, batch_tile=self.batch_tile,
            chunk=self.chunk, interpret=self.interpret, t_max=t_max,
            cell_dtype=cell_dtype, xdrop=xdrop)
        if collect_tb and decode == "device":
            # Apply the lockstep walker to the kernel's TBM block: the
            # packed plane stays in device memory and only the RLE CIGAR
            # arrays become host-fetch candidates.
            from repro.core.traceback_device import device_decode_result
            out = device_decode_result(out, n, m, band=band, mode=mode)
        return out

    def run_persistent(self, groups, *, sc, adaptive=True, collect_tb=True,
                       mode="global", decode="device", cell_dtype="int32",
                       xdrop=None):
        """All dispatch groups through ONE megakernel launch (contract in
        `core.backends`). `groups` is a sequence of
        (q_pad, r_pad, n, m, band, t_max) tuples; returns the merged
        group-major result dict as device arrays."""
        if collect_tb and decode != "device":
            raise ValueError(
                "persistent dispatch fuses the traceback decode on-device;"
                " decode='host' exists only on the pipelined path")
        bt = self.batch_tile
        geom = tuple(
            (int(q.shape[1]), int(r.shape[1]), int(band),
             None if t_max is None else int(t_max), int(q.shape[0]))
            for (q, r, n, m, band, t_max) in groups)
        fn = _persistent_program(sc, adaptive, collect_tb, mode, cell_dtype,
                                 geom, bt, self.chunk, self.interpret,
                                 xdrop)
        return fn(*_stack_groups(groups, geom, bt))


def _stack_groups(groups, geom, bt):
    """Stack ragged per-group arrays into the megakernel's uniform
    (G, nb_max, bt, L_max) layout (host-side, once per request). Padding
    rows are dummy length-1 pairs (base fill 4), padding tiles/columns
    are never read by the masked grid."""
    G = len(geom)
    Lq_max = max(gm[0] for gm in geom)
    Lr_max = max(gm[1] for gm in geom)
    nb_max = max(-(-gm[4] // bt) for gm in geom)
    rows = nb_max * bt
    q_st = np.full((G, rows, Lq_max), 4, np.int8)
    r_st = np.full((G, rows, Lr_max), 4, np.int8)
    n_st = np.ones((G, rows), np.int32)
    m_st = np.ones((G, rows), np.int32)
    for g, (q, r, n, m, _, _) in enumerate(groups):
        n_pad, lq = q.shape
        q_st[g, :n_pad, :lq] = np.asarray(q, np.int8)
        r_st[g, :n_pad, :r.shape[1]] = np.asarray(r, np.int8)
        n_st[g, :n_pad] = np.asarray(n, np.int32)
        m_st[g, :n_pad] = np.asarray(m, np.int32)
    return (q_st.reshape(G, nb_max, bt, Lq_max),
            r_st.reshape(G, nb_max, bt, Lr_max),
            n_st.reshape(G, nb_max, bt, 1),
            m_st.reshape(G, nb_max, bt, 1))


@functools.lru_cache(maxsize=128)
def _persistent_program(sc, adaptive, collect_tb, mode, cell_dtype, geom,
                        bt, chunk, interpret, xdrop):
    """Build + jit the single-launch megakernel program for one request
    signature. The per-group scalar table (band / live chunk count /
    live tile count) is derived from the static geometry here and closed
    over as the scalar-prefetch dispatch queue; the cache makes repeat
    requests of the same signature launch with zero retracing."""
    from repro.core.backends import merge_persistent_outputs
    from repro.core.traceback_device import device_decode_result
    from repro.kernels.banded_dp.persistent import persistent_align_pallas

    band_arr = np.array([gm[2] for gm in geom], np.int32)
    chunks_arr = np.array(
        [-(-(gm[3] if gm[3] is not None else gm[0] + gm[1]) // chunk)
         for gm in geom], np.int32)
    ntiles_arr = np.array([-(-gm[4] // bt) for gm in geom], np.int32)

    def program(q_st, r_st, n_st, m_st):
        outs = persistent_align_pallas(
            q_st, r_st, n_st, m_st, band_arr, chunks_arr, ntiles_arr,
            sc=sc, geom=geom, bt=bt, chunk=chunk, adaptive=adaptive,
            collect_tb=collect_tb, mode=mode, interpret=interpret,
            cell_dtype=cell_dtype, xdrop=xdrop)
        merged = []
        nb_max = q_st.shape[1]
        for g, (q_len, r_len, band, t_max, n_pad) in enumerate(geom):
            o = outs[g]
            if collect_tb:
                n_g = n_st[g].reshape(nb_max * bt)[:n_pad]
                m_g = m_st[g].reshape(nb_max * bt)[:n_pad]
                o = device_decode_result(o, n_g, m_g, band=band, mode=mode)
            merged.append(o)
        return merge_persistent_outputs(merged)

    return jax.jit(program)


BACKEND = PallasBackend
