"""Persistent Pallas dispatch megakernel: one grid over ALL groups.

The pipelined path launches one `pallas_call` per dispatch group and lets
the host mediate group boundaries. This kernel makes the whole request a
single device-side loop — the paper's in-situ dataflow (§V-C): the grid
is

    (G groups, nb_max batch tiles, n_chunks_max step chunks)

with the step-chunk axis innermost, so VMEM band-state scratch persists
per (group, tile) across its chunk sweep exactly as in the per-group
kernel, and Pallas's grid pipeline double-buffers the next block's
HBM->VMEM sequence streams behind the current chunk's compute. Per-group
raggedness is handled on-device instead of by the host:

  * per-group trimmed sweep — `pl.when(c < chunks[g])` masks the step
    chunks past the group's t_max (§VI-F trip count), so a short group
    never sweeps the long group's dead diagonals;
  * per-group band width — the kernel is built at B_max = max band and
    lanes >= band[g] are folded into the dead-cell mask every step.
    Every neighbour read is liveness-gated, so a dead lane behaves
    exactly like the out-of-band fill of a B=band[g] kernel: results are
    bit-exact with the per-group pipeline (asserted by
    tests/test_persistent_dispatch.py);
  * per-group tile counts — `pl.when(b < ntiles[g])` skips padding tiles.

The per-group scalars (band, chunk count, tile count) ride in front of
the grid as scalar-prefetch operands (`PrefetchScalarGridSpec`), i.e.
they are on-chip before the first block arrives — the group table IS the
device-side dispatch queue, and no host sync happens anywhere in the
sweep. With `cell_dtype="narrow"` the persistent VMEM band state is int8
diffs + int16 band-relative H (paper §IV bit-width reduction; see
`kernels.banded_dp.banded_dp`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.banded import DEAD16, pack_tb_lanes, packed_tb_width
from repro.core.scoring import ScoringConfig
from repro.kernels.banded_dp.banded_dp import (DEAD, NEG, STATS_W, STRIP,
                                               _BEST, _BEST_I, _BEST_J,
                                               _FINAL_LO, _PBEST, _SCORE,
                                               _STATUS, _move_windows,
                                               _pick_lane, _seq_strip,
                                               _shift_away_lane0,
                                               _shift_toward_lane0,
                                               pad_seq_lanes, padded_seq_len,
                                               resolve_interpret)


def _persistent_kernel(sc: ScoringConfig, B_max: int, chunk: int,
                       adaptive: bool, bt: int, mode: str, collect_tb: bool,
                       cell_dtype: str, xdrop: int | None,
                       # scalar prefetch (the device-side dispatch queue)
                       band_ref, chunks_ref, ntiles_ref,
                       # blocks
                       q_ref, r_ref, n_ref, m_ref,
                       tb_ref, lo_out_ref, stats_ref,
                       u_s, v_s, x_s, y_s, H_s, lo_s, base_s,
                       qw_s, rw_s,  # sequence windows under the band
                       alive_s):  # SMEM all-retired chunk-skip flag
    o, e = sc.gap_open, sc.gap_extend
    oe = jnp.int32(o + e)
    shift = jnp.int32(2 * (o + e))
    B = B_max
    narrow = cell_dtype == "narrow"
    cdt = jnp.int8 if narrow else jnp.int32
    hdt = jnp.int16 if narrow else jnp.int32
    g = pl.program_id(0)
    cblk = pl.program_id(2)
    band_g = band_ref[g]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (bt, B), 1)
    q_blk = q_ref.at[0, 0]  # (bt, Lq_pad)
    r_blk = r_ref.at[0, 0]

    live = (pl.program_id(1) < ntiles_ref[g]) & (cblk < chunks_ref[g])
    if xdrop is not None:
        # Per-(group, tile) all-retired chunk skip. The cblk == 0 OR-arm
        # covers the uninitialised flag before this tile's _init ran.
        live = live & ((cblk == 0) | (alive_s[0] != 0))

    @pl.when(live)
    def _body():
        @pl.when(cblk == 0)
        def _init():
            z = jnp.zeros((bt, B), cdt)
            u_s[...] = z
            v_s[...] = z
            x_s[...] = z
            y_s[...] = z
            H_s[...] = jnp.where(lanes == 0, 0,
                                 DEAD16 if narrow else NEG).astype(hdt)
            lo_s[...] = jnp.zeros((bt, 1), jnp.int32)
            base_s[...] = jnp.zeros((bt, 1), jnp.int32)
            # Diagonal 0 (lo = 0): lane k sits on row i = k, base q[k - 1].
            qw_s[...] = _shift_away_lane0(q_blk[:, :B], 4)
            rw_s[...] = jnp.full((bt, B), 4, jnp.int32)
            best0 = NEG if mode == "semiglobal" else 0
            cols = jax.lax.broadcasted_iota(jnp.int32, (bt, STATS_W), 1)
            stats_ref[0, 0] = jnp.where(cols == _SCORE, NEG,
                                        jnp.where(cols == _BEST, best0, 0))
            alive_s[0] = 1

        n = n_ref[0, 0].astype(jnp.int32)  # (bt, 1)
        m = m_ref[0, 0].astype(jnp.int32)
        in_lane = lanes < band_g        # dynamic-band lane mask

        def step(s, carry):
            u, v, x, y, H, lo, stats, qw, rw, sq, sr = carry
            t = cblk * chunk + s + 1

            # ---- direction (dynamic band width band_g) ----
            must_down = (lo + (n + m - t)) < (n - band_g + 1)
            must_right = lo >= n
            if adaptive:
                heur_right = H[:, :1] > _pick_lane(H, band_g - 1)
            else:
                heur_right = (2 * lo + band_g) * (n + m) >= 2 * t * n
            go_down = must_down | (~must_right & ~heur_right)
            lo_new = lo + go_down.astype(jnp.int32)

            def pick_up(a, fill):
                return jnp.where(go_down, a, _shift_away_lane0(a, fill))

            def pick_left(a, fill):
                return jnp.where(go_down, _shift_toward_lane0(a, fill), a)

            up_H = pick_up(H, NEG)
            up_x = pick_up(x, jnp.int32(0))
            up_v = pick_up(v, jnp.int32(0))
            left_H = pick_left(H, NEG)
            left_y = pick_left(y, jnp.int32(0))
            left_u = pick_left(u, jnp.int32(0))
            up_valid = up_H > DEAD
            left_valid = left_H > DEAD

            # ---- coordinates / masks; lanes beyond band_g are dead ----
            i_vec = lo_new + lanes
            j_vec = t - i_vec
            valid = ((i_vec >= 0) & (i_vec <= n) & (j_vec >= 0)
                     & (j_vec <= m) & in_lane)
            interior = valid & (i_vec >= 1) & (j_vec >= 1)
            brow = valid & (i_vec == 0) & (j_vec >= 1)
            bcol = valid & (j_vec == 0) & (i_vec >= 1)

            # The windows span all B_max lanes (dead lanes hold real
            # bases too), so they move exactly as in the per-group kernel.
            qb, rb, sq, sr = _move_windows(go_down, qw, rw, sq, sr)
            is_match = (qb == rb) & (qb < 4) & (rb < 4)
            s_sub = jnp.where(is_match, jnp.int32(sc.match),
                              jnp.int32(-sc.mismatch))

            # ---- Eq. (4) parallelized update ----
            x_arm = jnp.where(up_valid, up_x, NEG)
            y_arm = jnp.where(left_valid, left_y, NEG)
            v_up = jnp.where(up_valid, up_v, oe)
            u_left = jnp.where(left_valid, left_u, oe)
            diag_valid = up_valid | left_valid
            s_arm = jnp.where(diag_valid, s_sub + shift, NEG)

            a_new = jnp.maximum(jnp.maximum(s_arm, x_arm), y_arm)
            u_new = a_new - v_up
            v_new = a_new - u_left
            x_new = jnp.maximum(a_new, x_arm + o) - u_left
            y_new = jnp.maximum(a_new, y_arm + o) - v_up
            H_new = jnp.where(up_valid, up_H + u_new - oe,
                              jnp.where(left_valid, left_H + v_new - oe,
                                        NEG))

            # ---- traceback flags ----
            if collect_tb:
                direction = jnp.where(a_new == s_arm, 0,
                                      jnp.where(a_new == x_arm, 1, 2))
                ext_e = ((x_arm + o) > a_new).astype(jnp.int32)
                ext_f = ((y_arm + o) > a_new).astype(jnp.int32)
                code = direction + 4 * ext_e + 8 * ext_f
                code = pack_tb_lanes(jnp.where(interior, code, 0))
            else:
                code = None

            # ---- boundary overrides ----
            ob = jnp.int32(o)
            if mode == "semiglobal":
                v_new = jnp.where(brow, oe, v_new)
                x_new = jnp.where(brow, oe, x_new)
            else:
                v_new = jnp.where(brow, jnp.where(j_vec == 1, 0, ob), v_new)
                x_new = jnp.where(brow, jnp.where(j_vec == 1, 0, ob), x_new)
            u_new = jnp.where(brow, ob, u_new)
            y_new = jnp.where(brow, ob, y_new)
            u_new = jnp.where(bcol, jnp.where(i_vec == 1, 0, ob), u_new)
            y_new = jnp.where(bcol, jnp.where(i_vec == 1, 0, ob), y_new)
            v_new = jnp.where(bcol, ob, v_new)
            x_new = jnp.where(bcol, ob, x_new)
            H_new = jnp.where(brow,
                              jnp.int32(0) if mode == "semiglobal"
                              else -(o + j_vec * e), H_new)
            H_new = jnp.where(bcol, -(o + i_vec * e), H_new)
            H_new = jnp.where(valid, H_new, NEG)
            u_new = jnp.where(valid, u_new, 0)
            v_new = jnp.where(valid, v_new, 0)
            x_new = jnp.where(valid, x_new, 0)
            y_new = jnp.where(valid, y_new, 0)

            # ---- xdrop retire rule + corner score capture ----
            done = t == (n + m)
            in_sweep = t <= (n + m)
            if xdrop is None:
                active = in_sweep
                status_new = stats[:, _STATUS:_STATUS + 1]
                pbest_new = stats[:, _PBEST:_PBEST + 1]
            else:
                # Same rule as the per-group kernel: retire when the live
                # band max fell > xdrop below the running best; ~done
                # keeps the corner step capturable.
                band_max = jnp.max(H_new, axis=1, keepdims=True)
                pb_new = jnp.maximum(stats[:, _PBEST:_PBEST + 1], band_max)
                status_prev = stats[:, _STATUS:_STATUS + 1]
                newly = in_sweep & (status_prev == 0) & ~done & \
                    (band_max < pb_new - jnp.int32(xdrop))
                status_new = jnp.where(newly, t, status_prev)
                active = in_sweep & (status_new == 0)
                pbest_new = jnp.where(active, pb_new,
                                      stats[:, _PBEST:_PBEST + 1])

            k_corner = jnp.clip(n - lo_new, 0, band_g - 1)
            h_corner = _pick_lane(H_new, k_corner)
            score_new = jnp.where(done & active, h_corner,
                                  stats[:, _SCORE:_SCORE + 1])
            flo_new = jnp.where(done & active, lo_new,
                                stats[:, _FINAL_LO:_FINAL_LO + 1])

            # ---- best-cell tracking ----
            elig = interior & active
            if mode == "semiglobal":
                elig = elig & (i_vec == n)
            H_masked = jnp.where(elig, H_new, NEG)
            cand = jnp.max(H_masked, axis=1, keepdims=True)
            k_best = jnp.min(jnp.where(H_masked == cand, lanes, B),
                             axis=1, keepdims=True)
            k_best = jnp.clip(k_best, 0, B - 1)
            best_prev = stats[:, _BEST:_BEST + 1]
            better = cand > best_prev
            best_new = jnp.where(better, cand, best_prev)
            bi_new = jnp.where(better, lo_new + k_best,
                               stats[:, _BEST_I:_BEST_I + 1])
            bj_new = jnp.where(better, t - lo_new - k_best,
                               stats[:, _BEST_J:_BEST_J + 1])
            stats_new = jnp.concatenate(
                [score_new, flo_new, best_new, bi_new, bj_new,
                 status_new, pbest_new, stats[:, _PBEST + 1:]], axis=1)

            # ---- carry freeze past the final diagonal / once retired ----
            u = jnp.where(active, u_new, u)
            v = jnp.where(active, v_new, v)
            x = jnp.where(active, x_new, x)
            y = jnp.where(active, y_new, y)
            H = jnp.where(active, H_new, H)
            lo = jnp.where(active, lo_new, lo)
            qw = jnp.where(active, qb, qw)
            rw = jnp.where(active, rb, rw)

            if collect_tb:
                tb_ref[0, 0, s] = code
                lo_out_ref[0, 0, s] = lo[:, 0]
            return (u, v, x, y, H, lo, stats_new, qw, rw, sq, sr)

        if narrow:
            # Widen before comparing: the VPU has no int16 compare.
            H_rel = H_s[...].astype(jnp.int32)
            H0 = jnp.where(H_rel <= DEAD16, NEG, base_s[...] + H_rel)
        else:
            H0 = H_s[...]
        lo0 = lo_s[...]
        # Entering bases of this chunk (see the per-group kernel).
        sq = _seq_strip(q_blk, lo0 + (B - 1))
        sr = _seq_strip(r_blk, cblk * chunk - lo0)
        carry = (u_s[...].astype(jnp.int32), v_s[...].astype(jnp.int32),
                 x_s[...].astype(jnp.int32), y_s[...].astype(jnp.int32),
                 H0, lo0, stats_ref[0, 0], qw_s[...], rw_s[...], sq, sr)
        u, v, x, y, H, lo, stats, qw, rw, _, _ = jax.lax.fori_loop(
            0, chunk, step, carry)
        if narrow:
            live = H > DEAD
            base = jnp.max(jnp.where(live, H, NEG), axis=1, keepdims=True)
            rel = jnp.maximum(H - base, jnp.int32(DEAD16 + 1))
            H_s[...] = jnp.where(live, rel,
                                 jnp.int32(DEAD16)).astype(jnp.int16)
            base_s[...] = base
        else:
            H_s[...] = H
        u_s[...] = u.astype(cdt)
        v_s[...] = v.astype(cdt)
        x_s[...] = x.astype(cdt)
        y_s[...] = y.astype(cdt)
        lo_s[...] = lo
        qw_s[...] = qw
        rw_s[...] = rw
        stats_ref[0, 0] = stats
        if xdrop is not None:
            # Drop the flag once every pair of this (group, tile) is
            # xdrop-retired or past its true trip count: the tile's
            # remaining step chunks short-circuit via the `live` gate.
            t_end = (cblk + 1) * chunk
            pair_done = (stats[:, _STATUS] != 0) | ((n + m)[:, 0] <= t_end)
            alive_s[0] = 1 - jnp.all(pair_done).astype(jnp.int32)


def persistent_align_pallas(q_st, r_st, n_st, m_st, band_arr, chunks_arr,
                            ntiles_arr, *, sc: ScoringConfig, geom: tuple,
                            bt: int, chunk: int, adaptive: bool,
                            collect_tb: bool, mode: str,
                            interpret: bool | None = None,
                            cell_dtype: str = "int32",
                            xdrop: int | None = None):
    """Run the persistent megakernel over a stacked multi-group request.

    Args:
      q_st/r_st: (G, nb_max, bt, Lq_max/Lr_max) int8 stacked sequences
        (padding tiles filled with base 4).
      n_st/m_st: (G, nb_max, bt, 1) int32 true lengths (1 for padding).
      band_arr/chunks_arr/ntiles_arr: (G,) int32 per-group band width,
        live step-chunk count (ceil(T_g / chunk)) and live tile count —
        the scalar-prefetch dispatch queue.
      geom: static per-group geometry, tuple of
        (q_len, r_len, band, t_max, N_pad) — N_pad counts the caller's
        padded rows (<= nb_max * bt), used to slice each group's rows
        out of the uniform grid output.

    Returns a list of per-group result dicts shaped exactly like
    `banded_align_pallas`'s output for that group (scores always; packed
    'tb'/'los' planes when collect_tb, trimmed to the group's sweep
    length but Bp_max wide — `pack_tb_lanes` is positional, so decoding
    with the group's own band width reads identical nibbles).
    """
    if chunk > STRIP:
        raise ValueError(f"chunk={chunk} exceeds the strip width {STRIP}")
    G, nb_max = q_st.shape[:2]
    Lq = padded_seq_len(q_st.shape[3])
    Lr = padded_seq_len(r_st.shape[3])
    B_max = max(gm[2] for gm in geom)
    n_chunks_max = int(max(chunks_arr))
    T_pad_max = n_chunks_max * chunk
    Bp = packed_tb_width(B_max)
    narrow = cell_dtype == "narrow"
    cdt = jnp.int8 if narrow else jnp.int32
    hdt = jnp.int16 if narrow else jnp.int32

    kernel = functools.partial(_persistent_kernel, sc, B_max, chunk,
                               adaptive, bt, mode, collect_tb, cell_dtype,
                               xdrop)
    grid = (G, nb_max, n_chunks_max)
    stats_shape = jax.ShapeDtypeStruct((G, nb_max, bt, STATS_W), jnp.int32)
    stats_spec = pl.BlockSpec((1, 1, bt, STATS_W),
                              lambda g, b, c, *_: (g, b, 0, 0))
    if collect_tb:
        out_shapes = (
            jax.ShapeDtypeStruct((G, nb_max, T_pad_max, bt, Bp), jnp.uint8),
            jax.ShapeDtypeStruct((G, nb_max, T_pad_max, bt), jnp.int32),
            stats_shape,
        )
        out_specs = (
            pl.BlockSpec((1, 1, chunk, bt, Bp),
                         lambda g, b, c, *_: (g, b, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, bt),
                         lambda g, b, c, *_: (g, b, c, 0)),
            stats_spec,
        )
    else:
        out_shapes = (stats_shape,)
        out_specs = (stats_spec,)
    in_specs = [
        pl.BlockSpec((1, 1, bt, Lq), lambda g, b, c, *_: (g, b, 0, 0)),
        pl.BlockSpec((1, 1, bt, Lr), lambda g, b, c, *_: (g, b, 0, 0)),
        pl.BlockSpec((1, 1, bt, 1), lambda g, b, c, *_: (g, b, 0, 0)),
        pl.BlockSpec((1, 1, bt, 1), lambda g, b, c, *_: (g, b, 0, 0)),
    ]
    scratch_shapes = [
        pltpu.VMEM((bt, B_max), cdt),       # u
        pltpu.VMEM((bt, B_max), cdt),       # v
        pltpu.VMEM((bt, B_max), cdt),       # x
        pltpu.VMEM((bt, B_max), cdt),       # y
        pltpu.VMEM((bt, B_max), hdt),       # H (base-relative if narrow)
        pltpu.VMEM((bt, 1), jnp.int32),     # lo
        pltpu.VMEM((bt, 1), jnp.int32),     # base
        pltpu.VMEM((bt, B_max), jnp.int32), # query window
        pltpu.VMEM((bt, B_max), jnp.int32), # reference window
        pltpu.SMEM((1,), jnp.int32),        # alive (xdrop chunk skip)
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    def dispatch_kernel(band_ref, chunks_ref, ntiles_ref,
                        q_ref, r_ref, n_ref, m_ref, *rest):
        # Without collect_tb there are no tb/lo outputs in `rest`.
        if collect_tb:
            tb_r, lo_r, st_r = rest[:3]
            scratch = rest[3:]
        else:
            tb_r, lo_r = None, None
            st_r = rest[0]
            scratch = rest[1:]
        kernel(band_ref, chunks_ref, ntiles_ref, q_ref, r_ref, n_ref,
               m_ref, tb_r, lo_r, st_r, *scratch)

    outs = pl.pallas_call(
        dispatch_kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(band_arr, jnp.int32), jnp.asarray(chunks_arr, jnp.int32),
      jnp.asarray(ntiles_arr, jnp.int32),
      pad_seq_lanes(jnp.asarray(q_st, jnp.int32), Lq),
      pad_seq_lanes(jnp.asarray(r_st, jnp.int32), Lr),
      jnp.asarray(n_st, jnp.int32), jnp.asarray(m_st, jnp.int32))

    stats = outs[-1]
    results = []
    for gi, (q_len, r_len, band, t_max, n_pad) in enumerate(geom):
        T_g = int(t_max) if t_max is not None else q_len + r_len
        st = stats[gi].reshape(nb_max * bt, STATS_W)[:n_pad]
        out = {"score": st[:, _SCORE], "final_lo": st[:, _FINAL_LO],
               "best_score": st[:, _BEST], "best_i": st[:, _BEST_I],
               "best_j": st[:, _BEST_J], "status": st[:, _STATUS]}
        if collect_tb:
            tb_g = (outs[0][gi].transpose(0, 2, 1, 3)
                    .reshape(nb_max * bt, T_pad_max, Bp)[:n_pad, :T_g])
            los_g = (outs[1][gi].transpose(0, 2, 1)
                     .reshape(nb_max * bt, T_pad_max)[:n_pad, :T_g])
            los_g = jnp.concatenate(
                [jnp.zeros((n_pad, 1), jnp.int32), los_g], axis=1)
            out["tb"] = tb_g
            out["los"] = los_g
        results.append(out)
    return results
