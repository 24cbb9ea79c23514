"""Pallas TPU kernel: in-VMEM adaptive banded parallelized DP wavefront.

TPU adaptation of the RAPIDx compute memory (CM, paper Fig. 5/6): the band
state — the four shifted difference vectors, the 32-bit H band, and the
band offset — lives in **VMEM scratch for the entire sweep**, exactly as
RAPIDx keeps it resident in the ReRAM subarray ("in-situ alignment", §V-C).
Sequences stream in once; only the 4-bit traceback flags stream out to HBM
(the TBM analogue). Per wavefront step the kernel does a handful of 8x128
VPU vector ops — the row-parallel PIM operations. The sequence bases under
the band ride in the carry as two B-lane windows moved by the same one-lane
shifts as the band state (the peripheral *shifter*): a down step keeps
every lane's j and shifts the query window by one lane, a right step keeps
i and shifts the reference window the other way, so each step one new base
enters one window per pair. The entering bases come from a per-chunk
128-lane strip of each sequence, cut once per step chunk at each pair's
own offset (`_seq_strip`). Every vector op is one Mosaic lowers: lane
shifts, selects, lane reductions and same-shape lane gathers.

Parallelism mapping (paper Fig. 6):
  * wavefront level  -> lane dimension (band B, up to 128 lanes)
  * sequence level   -> sublane dimension (batch tile `bt` pairs)
  * alignment-matrix -> the four fused vector updates per step
  * tile level       -> grid over batch tiles (and shard_map over chips)

Grid layout: (num_batch_tiles, num_step_chunks). TPU grids execute
sequentially, so scratch persists across the step-chunk axis; each chunk
advances the wavefront `chunk` steps and writes one (chunk, bt, B) block
of traceback flags. State is (re)initialised when the chunk index is 0.

Storage precision: band state is computed in int32 (native VPU lane width)
and the difference quantities provably fit the paper's 5-bit range. The
traceback plane is packed **two 4-bit flags per uint8 byte** in-register
before the TBM store (`core.banded.pack_tb_lanes` layout: even lane in the
low nibble), so the per-step store is ceil(B/2) bytes per pair — half the
TBM traffic of a one-flag-per-byte plane. See DESIGN.md §5/§6.

`interpret=None` (the default of every entry point) resolves in one
place, `default_interpret`: compiled on a TPU, interpreted elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.banded import DEAD16, pack_tb_lanes, packed_tb_width
from repro.core.scoring import ScoringConfig

NEG = -(1 << 28)   # plain ints: pallas kernels must not capture jax arrays
DEAD = -(1 << 27)
_I32_MIN = -(1 << 31)

#: Lanes per sequence strip (one vreg row): the bases a pair's windows can
#: take in over one step chunk. Chunks are capped at this many steps.
STRIP = 128


def default_interpret() -> bool:
    """Interpret the kernels unless a TPU is attached (the one place the
    `interpret=None` default of the kernel entry points resolves)."""
    return jax.devices()[0].platform != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """`interpret`, or the platform default when it is None."""
    return default_interpret() if interpret is None else bool(interpret)


def _shift_toward_lane0(a, fill):
    """result[:, k] = a[:, k+1]; last lane <- fill (scalar or (bt, 1))."""
    col = jnp.broadcast_to(jnp.asarray(fill, a.dtype), a[:, :1].shape)
    return jnp.concatenate([a[:, 1:], col], axis=1)


def _shift_away_lane0(a, fill):
    """result[:, k] = a[:, k-1]; lane 0 <- fill (scalar or (bt, 1))."""
    col = jnp.broadcast_to(jnp.asarray(fill, a.dtype), a[:, :1].shape)
    return jnp.concatenate([col, a[:, :-1]], axis=1)


def _pick_lane(a, k):
    """a[p, k[p]] as (bt, 1): a masked lane reduction (k is (bt, 1) or a
    scalar). Exactly one lane matches, so the max is that lane's value."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    return jnp.max(jnp.where(lanes == k, a, _I32_MIN), axis=1,
                   keepdims=True)


def padded_seq_len(L: int) -> int:
    """Lane length a sequence block is padded to: whole strips, plus two
    spare strips so a strip cut at any in-range offset stays in bounds."""
    return -(-L // STRIP) * STRIP + 2 * STRIP


def pad_seq_lanes(x, L_pad: int):
    """Pad the last (lane) axis of a sequence block to L_pad with base 4."""
    pad = [(0, 0)] * (x.ndim - 1) + [(0, L_pad - x.shape[-1])]
    return jnp.pad(x, pad, constant_values=4)


def _seq_strip(seq_ref, off):
    """strip[p, c] = seq[p, off[p] + c] for c < STRIP: each pair's bases
    from its own offset (off is (bt, 1), clipped into range).

    Cut in two stages Mosaic lowers: a masked select over the row's
    128-lane blocks picks blocks off//128 and off//128 + 1, then one
    same-shape lane gather per block rotates them by off % 128.
    """
    bt, L = seq_ref.shape
    nblk = L // STRIP
    off = jnp.clip(off, 0, L - STRIP - 1)
    blk = off // STRIP
    zero = jnp.zeros((bt, STRIP), jnp.int32)

    def pick(b, acc):
        first, second = acc
        x = seq_ref[:, pl.ds(pl.multiple_of(b * STRIP, STRIP), STRIP)]
        return (jnp.where(blk == b, x, first),
                jnp.where(blk + 1 == b, x, second))

    first, second = jax.lax.fori_loop(0, nblk, pick, (zero, zero))
    idx = jax.lax.broadcasted_iota(jnp.int32, (bt, STRIP), 1) + off % STRIP
    rot = idx % STRIP
    return jnp.where(idx < STRIP,
                     jnp.take_along_axis(first, rot, axis=1),
                     jnp.take_along_axis(second, rot, axis=1))


def _move_windows(go_down, qw, rw, sq, sr):
    """Advance the sequence windows with the band (the peripheral shifter).

    qw[:, k] holds q[i_k - 1] and rw[:, k] holds r[j_k - 1] for band cell
    k = (i_k, j_k). A down step keeps every lane's j and moves i by one,
    so the query window shifts toward lane 0 and takes the next query
    base from the strip; a right step keeps i, so the reference window
    shifts away from lane 0 and takes the next reference base. Each strip
    shifts once per base it hands out.
    """
    qw = jnp.where(go_down, _shift_toward_lane0(qw, sq[:, :1]), qw)
    rw = jnp.where(go_down, rw, _shift_away_lane0(rw, sr[:, :1]))
    sq = jnp.where(go_down, _shift_toward_lane0(sq, 0), sq)
    sr = jnp.where(go_down, sr, _shift_toward_lane0(sr, 0))
    return qw, rw, sq, sr


# Column layout of the (bt, STATS_W) stats plane (the per-pair scalar
# results carried across step chunks and streamed out once at the end).
# _STATUS: 0 = live/aligned, k > 0 = xdrop-retired at wavefront step k.
# _PBEST: the pair's running live-band max H (the xdrop reference point).
STATS_W = 8
_SCORE, _FINAL_LO, _BEST, _BEST_I, _BEST_J = 0, 1, 2, 3, 4
_STATUS, _PBEST = 5, 6


def _wavefront_kernel(sc: ScoringConfig, band: int, chunk: int,
                      adaptive: bool, bt: int, mode: str, collect_tb: bool,
                      cell_dtype: str, xdrop: int | None,
                      # refs
                      q_ref, r_ref, n_ref, m_ref,          # inputs
                      tb_ref, lo_out_ref, stats_ref,        # outputs
                      u_s, v_s, x_s, y_s, H_s, lo_s, base_s,  # scratch
                      qw_s, rw_s,  # sequence windows under the band
                      alive_s):  # SMEM all-retired chunk-skip flag
    o, e = sc.gap_open, sc.gap_extend
    oe = jnp.int32(o + e)
    shift = jnp.int32(2 * (o + e))
    B = band
    narrow = cell_dtype == "narrow"
    cdt = jnp.int8 if narrow else jnp.int32
    hdt = jnp.int16 if narrow else jnp.int32
    h_dead = DEAD16 if narrow else NEG
    tblk = pl.program_id(1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (bt, B), 1)

    @pl.when(tblk == 0)
    def _init():
        z = jnp.zeros((bt, B), cdt)
        u_s[...] = z
        v_s[...] = z
        x_s[...] = z
        y_s[...] = z
        H_s[...] = jnp.where(lanes == 0, 0, h_dead).astype(hdt)
        lo_s[...] = jnp.zeros((bt, 1), jnp.int32)
        base_s[...] = jnp.zeros((bt, 1), jnp.int32)
        # Diagonal 0 (lo = 0): lane k sits on row i = k, base q[k - 1].
        qw_s[...] = _shift_away_lane0(q_ref[:, :B], 4)
        rw_s[...] = jnp.full((bt, B), 4, jnp.int32)
        best0 = NEG if mode == "semiglobal" else 0
        cols = jax.lax.broadcasted_iota(jnp.int32, (bt, STATS_W), 1)
        stats_ref[...] = jnp.where(cols == _SCORE, NEG,
                                   jnp.where(cols == _BEST, best0, 0))
        alive_s[0] = 1

    n = n_ref[...].astype(jnp.int32)  # (bt, 1)
    m = m_ref[...].astype(jnp.int32)

    def step(s, carry):
        u, v, x, y, H, lo, stats, qw, rw, sq, sr = carry
        t = tblk * chunk + s + 1  # global wavefront step (diag index)

        # ---- direction (paper §IV-B2 + feasibility clamps) ----
        must_down = (lo + (n + m - t)) < (n - B + 1)
        must_right = lo >= n
        if adaptive:
            heur_right = H[:, :1] > H[:, B - 1:]
        else:
            heur_right = (2 * lo + B) * (n + m) >= 2 * t * n
        go_down = must_down | (~must_right & ~heur_right)  # (bt,1)
        lo_new = lo + go_down.astype(jnp.int32)

        # ---- neighbour alignment (the peripheral shifter) ----
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

        # ---- coordinates / masks / substitution scores ----
        i_vec = lo_new + lanes          # (bt, B)
        j_vec = t - i_vec
        valid = (i_vec >= 0) & (i_vec <= n) & (j_vec >= 0) & (j_vec <= m)
        interior = valid & (i_vec >= 1) & (j_vec >= 1)
        brow = valid & (i_vec == 0) & (j_vec >= 1)
        bcol = valid & (j_vec == 0) & (i_vec >= 1)

        # Bases under the moved band; exact on interior cells, which are
        # the only cells whose substitution score reaches an output.
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
                          jnp.where(left_valid, left_H + v_new - oe, NEG))

        # ---- traceback flags ----
        if collect_tb:
            direction = jnp.where(a_new == s_arm, 0,
                                  jnp.where(a_new == x_arm, 1, 2))
            ext_e = ((x_arm + o) > a_new).astype(jnp.int32)
            ext_f = ((y_arm + o) > a_new).astype(jnp.int32)
            code = direction + 4 * ext_e + 8 * ext_f
            code = jnp.where(interior, code, 0)
            # Pack two lanes per byte in-register: only the packed
            # (bt, ceil(B/2)) rows ever reach the TBM store below.
            code = pack_tb_lanes(code)
        else:
            code = None

        # ---- boundary overrides ----
        ob = jnp.int32(o)
        if mode == "semiglobal":
            # Free leading reference gap: H(0,j) = 0 for all j.
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
        done = t == (n + m)  # (bt,1)
        in_sweep = t <= (n + m)
        if xdrop is None:
            active = in_sweep
            status_new = stats[:, _STATUS:_STATUS + 1]
            pbest_new = stats[:, _PBEST:_PBEST + 1]
        else:
            # Retire a pair the first step its live-band max H drops more
            # than xdrop below its running best (dead cells are NEG).
            # ~done keeps the corner step capturable: a pair never
            # retires on its final diagonal.
            band_max = jnp.max(H_new, axis=1, keepdims=True)
            pb_new = jnp.maximum(stats[:, _PBEST:_PBEST + 1], band_max)
            status_prev = stats[:, _STATUS:_STATUS + 1]
            newly = in_sweep & (status_prev == 0) & ~done & \
                (band_max < pb_new - jnp.int32(xdrop))
            status_new = jnp.where(newly, t, status_prev)
            active = in_sweep & (status_new == 0)
            pbest_new = jnp.where(active, pb_new,
                                  stats[:, _PBEST:_PBEST + 1])

        k_corner = jnp.clip(n - lo_new, 0, B - 1)  # (bt,1)
        h_corner = _pick_lane(H_new, k_corner)
        # done & active: a retired pair's frozen-carry recompute must not
        # leak into the capture (no-op when xdrop is None: done => active).
        score_new = jnp.where(done & active, h_corner,
                              stats[:, _SCORE:_SCORE + 1])
        flo_new = jnp.where(done & active, lo_new,
                            stats[:, _FINAL_LO:_FINAL_LO + 1])

        # ---- extension/local best-cell tracking (paper §III-A2) ----
        elig = interior & active
        if mode == "semiglobal":
            elig = elig & (i_vec == n)
        H_masked = jnp.where(elig, H_new, NEG)
        cand = jnp.max(H_masked, axis=1, keepdims=True)
        # First (smallest-k) maximising lane — matches jnp.argmax ties.
        k_best = jnp.min(jnp.where(H_masked == cand, lanes, B), axis=1,
                         keepdims=True)
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

        # ---- carry freeze past the final diagonal (and once retired) ----
        u = jnp.where(active, u_new, u)
        v = jnp.where(active, v_new, v)
        x = jnp.where(active, x_new, x)
        y = jnp.where(active, y_new, y)
        H = jnp.where(active, H_new, H)
        lo = jnp.where(active, lo_new, lo)
        qw = jnp.where(active, qb, qw)
        rw = jnp.where(active, rb, rw)

        # ---- stream traceback + band offsets out (TBM write) ----
        if collect_tb:
            tb_ref[0, s] = code
            lo_out_ref[0, s] = lo[:, 0]
        return (u, v, x, y, H, lo, stats_new, qw, rw, sq, sr)

    def _sweep():
        # Widen the (possibly narrow) scratch carry to exact int32
        # registers for the step loop; narrow storage only exists at chunk
        # boundaries, and the base+relative reconstruction is exact, so
        # the loop values are bit-identical to the int32-scratch kernel.
        if narrow:
            # Widen before comparing: the VPU has no int16 compare.
            H_rel = H_s[...].astype(jnp.int32)
            H0 = jnp.where(H_rel <= DEAD16, NEG, base_s[...] + H_rel)
        else:
            H0 = H_s[...]
        lo0 = lo_s[...]
        # This chunk's entering bases: the query from row lo0 + B - 1 on
        # (down steps), the reference from column tblk * chunk - lo0 on
        # (right steps).
        sq = _seq_strip(q_ref, lo0 + (B - 1))
        sr = _seq_strip(r_ref, tblk * chunk - lo0)
        carry = (u_s[...].astype(jnp.int32), v_s[...].astype(jnp.int32),
                 x_s[...].astype(jnp.int32), y_s[...].astype(jnp.int32),
                 H0, lo0, stats_ref[...], qw_s[...], rw_s[...], sq, sr)
        u, v, x, y, H, lo, stats, qw, rw, _, _ = jax.lax.fori_loop(
            0, chunk, step, carry)
        if narrow:
            # Re-narrow for the chunk-boundary store: base = max live H
            # per pair; live cells keep H - base (in [-spread_bound, 0],
            # proven int16-safe by `validate_narrow_cells`; the DEAD16+1
            # floor is a never-binding saturation guard). Dead cells ->
            # DEAD16 sentinel, diffs -> int8 (range [0, M + 2(o+e)]).
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
        stats_ref[...] = stats
        if xdrop is not None:
            # All-retired/finished chunk skip: once every pair of this
            # batch tile is either xdrop-retired or past its true trip
            # count, drop the flag so the remaining step chunks of this
            # tile short-circuit via the pl.when gate below.
            t_end = (tblk + 1) * chunk
            pair_done = (stats[:, _STATUS] != 0) | ((n + m)[:, 0] <= t_end)
            alive_s[0] = 1 - jnp.all(pair_done).astype(jnp.int32)

    if xdrop is None:
        _sweep()
    else:
        # tblk == 0 OR-arm: the flag is uninitialised before _init ran.
        pl.when((tblk == 0) | (alive_s[0] != 0))(_sweep)


def banded_align_pallas(q_pad, r_pad, n, m, *, sc: ScoringConfig, band: int,
                        adaptive: bool = True, collect_tb: bool = True,
                        mode: str = "global", batch_tile: int = 8,
                        chunk: int = 128, interpret: bool | None = None,
                        t_max: int | None = None,
                        cell_dtype: str = "int32",
                        xdrop: int | None = None):
    """pl.pallas_call wrapper. See ops.banded_align_kernel_batch for the
    public jit'd API (padding, reshaping, traceback plumbing).

    Args:
      q_pad: (N, Lq) int8/int32, N divisible by batch_tile.
      r_pad: (N, Lr).
      n, m: (N,) true lengths.
      band: band width B (lane dimension; <=128 keeps one VPU register row).
      collect_tb: stream traceback flags; False is the score-only fast
        path (no TBM traffic — the Fig. 14 "without traceback" mode).
      mode: "global" or "semiglobal" (free reference-end gaps).
      chunk: wavefront steps per grid step (traceback block height), at
        most STRIP.
      interpret: run the kernel body in interpret mode (CPU validation);
        None = `default_interpret()`.
      t_max: trimmed sweep length (must be >= max true n + m over the
        batch): the step-chunk grid shrinks to ceil(t_max / chunk)
        chunks, so a short-read batch in a long bucket stops sweeping
        dead diagonals. None = full Lq + Lr sweep.
      cell_dtype: "int32" or "narrow". Narrow keeps the persistent VMEM
        band state as int8 diffs + int16 band-relative H (+ one int32
        base per pair) — the paper §IV bit-width reduction, quartering
        scratch bytes per lane so wider bands fit the same VMEM budget.
        The step loop still computes int32 in registers; bit-exact under
        `core.banded.validate_narrow_cells` (callers enforce the guard).
      xdrop: X-drop early-exit threshold (see `core.banded.banded_align`).
        Retired pairs freeze their carry and report their retiring step in
        the 'status' output; once EVERY pair of a batch tile is retired or
        past its true trip count, an SMEM flag short-circuits the tile's
        remaining step chunks (`pl.when`), skipping their compute
        entirely. None = full sweep, bit-exact with today's kernel.
    """
    N, Lq = q_pad.shape
    Lr = r_pad.shape[1]
    bt = batch_tile
    if N % bt:
        raise ValueError(f"N={N} not divisible by batch_tile={bt}")
    if chunk > STRIP:
        raise ValueError(f"chunk={chunk} exceeds the strip width {STRIP}")
    nb = N // bt
    Lq_pad, Lr_pad = padded_seq_len(Lq), padded_seq_len(Lr)
    T = int(t_max) if t_max is not None else Lq + Lr
    T_pad = int(-(-T // chunk) * chunk)
    n_chunks = T_pad // chunk

    kernel = functools.partial(_wavefront_kernel, sc, band, chunk,
                               adaptive, bt, mode, collect_tb, cell_dtype,
                               xdrop)
    grid = (nb, n_chunks)

    stats_shape = jax.ShapeDtypeStruct((nb, bt, STATS_W), jnp.int32)
    stats_spec = pl.BlockSpec((1, bt, STATS_W), lambda b, t: (b, 0, 0))
    Bp = packed_tb_width(band)  # two 4-bit flags per tb byte
    if collect_tb:
        out_shapes = (
            jax.ShapeDtypeStruct((nb, T_pad, bt, Bp), jnp.uint8),  # tb
            jax.ShapeDtypeStruct((nb, T_pad, bt), jnp.int32),      # lo/diag
            stats_shape,
        )
        out_specs = (
            pl.BlockSpec((1, chunk, bt, Bp), lambda b, t: (b, t, 0, 0)),
            pl.BlockSpec((1, chunk, bt), lambda b, t: (b, t, 0)),
            stats_spec,
        )
    else:
        out_shapes = (stats_shape,)
        out_specs = (stats_spec,)
    in_specs = [
        pl.BlockSpec((1, bt, Lq_pad), lambda b, t: (b, 0, 0)),
        pl.BlockSpec((1, bt, Lr_pad), lambda b, t: (b, 0, 0)),
        pl.BlockSpec((1, bt, 1), lambda b, t: (b, 0, 0)),
        pl.BlockSpec((1, bt, 1), lambda b, t: (b, 0, 0)),
    ]
    cdt = jnp.int8 if cell_dtype == "narrow" else jnp.int32
    hdt = jnp.int16 if cell_dtype == "narrow" else jnp.int32
    scratch_shapes = [
        pltpu.VMEM((bt, band), cdt),        # u
        pltpu.VMEM((bt, band), cdt),        # v
        pltpu.VMEM((bt, band), cdt),        # x
        pltpu.VMEM((bt, band), cdt),        # y
        pltpu.VMEM((bt, band), hdt),        # H (base-relative if narrow)
        pltpu.VMEM((bt, 1), jnp.int32),     # lo
        pltpu.VMEM((bt, 1), jnp.int32),     # base (narrow H offset)
        pltpu.VMEM((bt, band), jnp.int32),  # query window
        pltpu.VMEM((bt, band), jnp.int32),  # reference window
        pltpu.SMEM((1,), jnp.int32),        # alive (xdrop chunk skip)
    ]

    def unsqueeze_kernel(q_r, r_r, n_r, m_r, *rest):
        # Blocks carry a leading size-1 grid dim; present 2-D views of the
        # inputs and stats to the kernel body. The tb/lo outputs keep it
        # (Mosaic refuses a dynamic row store through a sliced view).
        # Without collect_tb there are no tb/lo outputs.
        if collect_tb:
            tb_r, lo_r, st_r = rest[:3]
            scratch = rest[3:]
            kernel(q_r.at[0], r_r.at[0], n_r.at[0], m_r.at[0],
                   tb_r, lo_r, st_r.at[0], *scratch)
        else:
            st_r = rest[0]
            scratch = rest[1:]
            kernel(q_r.at[0], r_r.at[0], n_r.at[0], m_r.at[0],
                   None, None, st_r.at[0], *scratch)

    outs = pl.pallas_call(
        unsqueeze_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch_shapes,
        interpret=resolve_interpret(interpret),
    )(pad_seq_lanes(q_pad.reshape(nb, bt, Lq).astype(jnp.int32), Lq_pad),
      pad_seq_lanes(r_pad.reshape(nb, bt, Lr).astype(jnp.int32), Lr_pad),
      n.reshape(nb, bt, 1).astype(jnp.int32),
      m.reshape(nb, bt, 1).astype(jnp.int32))

    stats = outs[-1].reshape(N, STATS_W)
    out = {"score": stats[:, _SCORE], "final_lo": stats[:, _FINAL_LO],
           "best_score": stats[:, _BEST], "best_i": stats[:, _BEST_I],
           "best_j": stats[:, _BEST_J], "status": stats[:, _STATUS]}
    if collect_tb:
        tb, los = outs[0], outs[1]
        # Reassemble to (N, ...) batch-major layouts matching core.banded.
        tb = tb.transpose(0, 2, 1, 3).reshape(N, T_pad, Bp)[:, :T]
        los = los.transpose(0, 2, 1).reshape(N, T_pad)[:, :T]
        los = jnp.concatenate([jnp.zeros((N, 1), jnp.int32), los], axis=1)
        out["tb"] = tb
        out["los"] = los
    return out
