"""Adaptive banded parallelized DP alignment (paper §IV-B) — JAX reference.

This is the paper's core algorithm as a `lax.scan` over wavefront steps:

  * One scan step == one wavefront move (paper Fig. 4(c) / Fig. 6(c)): the
    band of B anti-diagonal cells advances one step right or down; total
    trip count is n + m ("the required number of iterations equals the sum
    of the two sequences' lengths", §VI-F).
  * The B band lanes update simultaneously — wavefront-level parallelism.
  * Within a step, all four shifted difference quantities (u'=dH', v'=dV',
    x'=dE', y'=dF') update in parallel from the shared intermediate A' and
    previous-step values only — alignment-matrix-level parallelism
    (paper Eq. (4); derivation in `core.diff_dp`).
  * The wavefront direction is adaptive (§IV-B2): if the H value of the
    rightmost band cell (lane 0 = smallest i = largest j) exceeds the
    leftmost (lane B-1), the band moves right, else down. Hard feasibility
    clamps guarantee the global-alignment corner (n, m) stays reachable.
  * Traceback flags (4 bits: 2-bit direction + E-extend + F-extend, paper
    §V-C3 "4-bit flags") stream out per step — the TBM analogue.

Band geometry: the grid is (n+1) x (m+1) with boundary row/col 0. On
anti-diagonal t the band covers rows i in [lo_t, lo_t + B); cell k is
(i, j) = (lo_t + k, t - lo_t - k). A down-move increments lo. Neighbor
alignment after a move is a +/-1 lane shift — the paper's peripheral
*shifter* circuit, realised here as a lane-select.

Batching (sequence-level parallelism, paper Fig. 6(b)) is `jax.vmap`;
tile-level parallelism (Fig. 6(a)) is `shard_map` in `core.distributed`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scoring import ScoringConfig

NEG = jnp.int32(-(1 << 28))
DEAD_THRESHOLD = -(1 << 27)

#: Steps per chunk of the xdrop early-exit sweep (`banded_align` with
#: ``xdrop`` set runs a `lax.while_loop` over chunks of this many scan
#: steps so a retired/finished pair stops paying for the rest of its
#: padded trip count). Matches the Pallas kernels' default step chunk
#: granularity closely enough that the CPU oracle sees the same
#: chunk-quantised savings the device does.
XDROP_CHUNK = 64

# ---------------------------------------------------------------------------
# Narrow-cell storage (paper §IV: the band-relative score spread is bounded
# by the band geometry, so 8/16-bit cells suffice — the bit-width reduction
# that drives RAPIDx's area/energy win). `cell_dtype="narrow"` keeps the
# wavefront carry as int8 difference planes (u/v/x/y are the shifted
# Eq. (4) quantities, always in [0, M + 2(o+e)]) plus an int16
# band-RELATIVE H with one int32 per-pair base (the running max live H).
# Every step reconstructs exact int32 values, runs the identical int32
# update, and re-narrows — so results are bit-exact with cell_dtype="int32"
# by construction whenever `validate_narrow_cells` accepts the config.
# ---------------------------------------------------------------------------

#: Dead-cell sentinel for the int16 band-relative H plane. Live cells
#: store H - base in [-(INT16_SPREAD_LIMIT), 0]; anything at or below
#: DEAD16 means "not alive" (reconstructed as NEG).
DEAD16 = -(1 << 14)

#: Max live band-relative spread representable without touching DEAD16.
INT16_SPREAD_LIMIT = (1 << 14) - 1

#: Max shifted difference value representable in the int8 u/v/x/y planes.
INT8_DIFF_LIMIT = 127


def narrow_spread_bound(sc: ScoringConfig, band: int) -> int:
    """Conservative bound on max(H) - min(H) over live cells of one band
    diagonal. Adjacent live lanes (i, j) and (i+1, j-1) differ by
    dH(i+1, j-1) - dV(i, j), each in [-(o+e), A + o + e], so one lane
    step moves H by at most A + 2(o+e); we additionally fold in the
    mismatch penalty B for slack against boundary-override cells. Summed
    over the band's B-1 lane gaps (rounded to `band` for headroom)."""
    return band * (sc.match + sc.mismatch + sc.shift)


def validate_narrow_cells(sc: ScoringConfig, band: int) -> None:
    """Static overflow guard for `cell_dtype="narrow"` (paper §IV bound:
    cell width is set by band x max-penalty, not sequence length).

    Raises ValueError when (band, scoring) cannot be proven safe for the
    int8 difference planes + int16 band-relative H carry. Called before
    tracing, so a bad config fails loudly instead of silently wrapping.
    """
    diff_max = sc.M + sc.shift
    if diff_max > INT8_DIFF_LIMIT:
        raise ValueError(
            f"narrow cells unsafe: shifted difference range "
            f"match + 2*(gap_open+gap_extend) = {diff_max} exceeds the "
            f"int8 limit {INT8_DIFF_LIMIT} for scoring {sc.name!r}; use "
            f"cell_dtype='int32' or a smaller-penalty scoring config")
    spread = narrow_spread_bound(sc, band)
    if spread > INT16_SPREAD_LIMIT:
        raise ValueError(
            f"narrow cells unsafe: band-relative score spread bound "
            f"band * (match + mismatch + 2*(gap_open+gap_extend)) = "
            f"{band} * {sc.match + sc.mismatch + sc.shift} = {spread} "
            f"exceeds the int16 live range {INT16_SPREAD_LIMIT}; shrink "
            f"the band below "
            f"{INT16_SPREAD_LIMIT // (sc.match + sc.mismatch + sc.shift)} "
            f"or use cell_dtype='int32'")

# ---------------------------------------------------------------------------
# Packed traceback-plane layout (paper §III / §V-C3: 4-bit flags are the
# whole point of RAPIDx's narrow-bit-width co-design — storing them one per
# byte would double TBM traffic). Two band lanes share one byte:
#
#     packed[..., b] = flags(lane 2b) | flags(lane 2b+1) << 4
#
# i.e. the EVEN lane rides the LOW nibble and the ODD lane the HIGH nibble.
# For odd band widths the last byte carries a single valid nibble (lane
# B-1 in its low nibble) and its high nibble is zero. See DESIGN.md §5.
# ---------------------------------------------------------------------------

#: Traceback flags packed per plane byte (two 4-bit flags).
TB_LANES_PER_BYTE = 2


def packed_tb_width(band: int) -> int:
    """Bytes per wavefront step of the packed traceback plane:
    ``ceil(band / 2)`` — the last byte is half-empty when ``band`` is odd."""
    return (band + 1) // 2


def pack_tb_lanes(code):
    """Pack 4-bit traceback flags two-per-byte along the last axis.

    ``code`` is any-rank uint8/int32 with lane axis last (values < 16);
    returns uint8 of shape ``(..., ceil(B / 2))`` in the low/high-nibble
    layout above. jnp-traceable: this runs inside the reference backend's
    `lax.scan` step and the Pallas kernel's register file, so the unpacked
    plane never exists in HBM or on the host. Implemented with ops the
    TPU kernel compiler lowers: a one-lane shift pairs each lane with its
    odd neighbour, then a same-shape lane gather moves lane 2b to lane b
    (no strided lane slice, no reshape that splits the minor axis).
    """
    code = code.astype(jnp.int32)
    B = code.shape[-1]
    nxt = jnp.concatenate([code[..., 1:], jnp.zeros_like(code[..., :1])],
                          axis=-1)
    pair = code | (nxt << 4)   # lane k: flags(k) | flags(k+1) << 4
    lane = jax.lax.broadcasted_iota(jnp.int32, code.shape, code.ndim - 1)
    even = jnp.take_along_axis(pair, jnp.minimum(2 * lane, B - 1),
                               axis=-1)
    return even[..., :packed_tb_width(B)].astype(jnp.uint8)


def select_tb_nibble(byte, lane):
    """4-bit flag of band lane ``lane`` from its packed plane byte
    (`pack_tb_lanes` layout: even lane = low nibble, odd lane = high).

    Written operator-wise so it serves both decoders: the host walkers
    pass numpy arrays, the device walker (`core.traceback_device`)
    passes traced jnp values.
    """
    return (byte >> ((lane & 1) * 4)) & 0xF


def unpack_tb_lanes(packed, band: int) -> np.ndarray:
    """Inverse of `pack_tb_lanes` (numpy, host-side).

    Debug/test helper only — the production decoders
    (`traceback_banded`, `traceback_banded_batch`) read nibbles straight
    from the packed plane and never materialise the unpacked layout.
    """
    packed = np.asarray(packed)
    out = np.empty((*packed.shape[:-1], packed.shape[-1] * 2), np.uint8)
    out[..., 0::2] = packed & 0xF
    out[..., 1::2] = packed >> 4
    return out[..., :band]


class BandState(NamedTuple):
    lo: jnp.ndarray        # int32 — top row of the band on the current diag
    u: jnp.ndarray         # (B,) int32|int8 — dH' (shifted)
    v: jnp.ndarray         # (B,) int32|int8 — dV'
    x: jnp.ndarray         # (B,) int32|int8 — dE' (combined term)
    y: jnp.ndarray         # (B,) int32|int8 — dF'
    H: jnp.ndarray         # (B,) int32 absolute — or int16 base-relative
    base: jnp.ndarray      # int32 — 0 (int32 cells) or the H base offset
    score: jnp.ndarray     # int32 — captured at t == n + m
    final_lo: jnp.ndarray  # int32 — lo at the final diagonal
    best: jnp.ndarray      # int32 — max H over all visited cells
    best_i: jnp.ndarray    # int32 — its coordinates (extension/local mode:
    best_j: jnp.ndarray    # "traceback starts from the max cell", §III-A2)
    pair_best: jnp.ndarray   # int32 — running max live-band H (xdrop ref)
    retired_at: jnp.ndarray  # int32 — 0 = live/aligned; k > 0 = the step
                             # at which the xdrop rule retired the pair


def _shift_down(a, fill):
    """result[k] = a[k-1]; result[0] = fill."""
    return jnp.concatenate([jnp.full((1,), fill, a.dtype), a[:-1]])


def _shift_up(a, fill):
    """result[k] = a[k+1]; result[B-1] = fill."""
    return jnp.concatenate([a[1:], jnp.full((1,), fill, a.dtype)])


def _init_state(band: int, mode: str = "global",
                cell_dtype: str = "int32") -> BandState:
    """Diagonal t=0: only cell (0,0) is alive, with H=0 and zero deltas."""
    if cell_dtype == "narrow":
        z = jnp.zeros((band,), jnp.int8)
        H = jnp.full((band,), DEAD16, jnp.int16).at[0].set(0)
    else:
        z = jnp.zeros((band,), jnp.int32)
        H = jnp.full((band,), NEG, jnp.int32).at[0].set(0)
    best0 = jnp.int32(NEG if mode == "semiglobal" else 0)
    return BandState(lo=jnp.int32(0), u=z, v=z, x=z, y=z, H=H,
                     base=jnp.int32(0), score=jnp.int32(NEG),
                     final_lo=jnp.int32(0), best=best0,
                     best_i=jnp.int32(0), best_j=jnp.int32(0),
                     pair_best=jnp.int32(0), retired_at=jnp.int32(0))


def _widen(state: BandState) -> tuple:
    """Exact int32 view of a (possibly narrow) carry: u/v/x/y widened,
    H reconstructed as base + Hrel with DEAD16-sentinel cells -> NEG."""
    u = state.u.astype(jnp.int32)
    v = state.v.astype(jnp.int32)
    x = state.x.astype(jnp.int32)
    y = state.y.astype(jnp.int32)
    if state.H.dtype == jnp.int16:
        H = jnp.where(state.H <= jnp.int16(DEAD16), NEG,
                      state.base + state.H.astype(jnp.int32))
    else:
        H = state.H
    return u, v, x, y, H


def _narrow(H_new, u_new, v_new, x_new, y_new, cell_dtype: str):
    """Re-narrow the freshly computed int32 planes for the carry.

    Narrow mode: base = max live H this diagonal (there is always at
    least one live cell while t <= n + m); live cells store H - base in
    int16, clamped at DEAD16 + 1 as a belt-and-braces saturation floor —
    `validate_narrow_cells` proves the clamp never binds. u/v/x/y are
    stored int8 (range [0, M + 2(o+e)], boundary overrides included).
    """
    if cell_dtype != "narrow":
        return H_new, u_new, v_new, x_new, y_new, jnp.int32(0)
    live = H_new > DEAD_THRESHOLD
    base = jnp.max(jnp.where(live, H_new, NEG))
    rel = jnp.maximum(H_new - base, jnp.int32(DEAD16 + 1))
    H16 = jnp.where(live, rel, jnp.int32(DEAD16)).astype(jnp.int16)
    return (H16, u_new.astype(jnp.int8), v_new.astype(jnp.int8),
            x_new.astype(jnp.int8), y_new.astype(jnp.int8), base)


def _step(sc: ScoringConfig, band: int, adaptive: bool, collect_tb: bool,
          mode: str, cell_dtype: str, xdrop: int | None, q_pad, r_pad, n, m,
          state: BandState, t):
    """One wavefront move: decide direction, advance band, update Eq. (4).

    The carry may be stored narrow (int8 diffs + int16 relative H); the
    update itself always runs in exact int32 — widen in, narrow out.

    With ``xdrop`` set, a pair retires the first step its live-band max
    falls more than ``xdrop`` below its running best; a retired pair
    freezes its carry exactly like the t > n + m freeze, so pairs that
    never trip the rule are bit-identical to an xdrop-off run.
    """
    o, e = sc.gap_open, sc.gap_extend
    oe = jnp.int32(o + e)
    shift = jnp.int32(2 * (o + e))
    B = band
    s_u, s_v, s_x, s_y, s_H = _widen(state)

    # ---- 1. Wavefront direction (paper §IV-B2 + feasibility clamps) ----
    lo = state.lo
    # Corner reachability: if we go right now, lo can still grow by at most
    # (n + m - t); the final diagonal must satisfy lo_final >= n - B + 1.
    must_down = (lo + (n + m - t)) < (n - B + 1)
    must_right = lo >= n
    if adaptive:
        # Rightmost band cell = lane 0 (largest j); leftmost = lane B-1.
        heur_right = s_H[0] > s_H[B - 1]
    else:
        # Fixed direction: steer the band centre toward the main diagonal
        # (the pre-defined scheme of Fig. 4(b), used by the Table V "No"
        # rows). Move down when centre row < t * n / (n + m).
        heur_right = (2 * lo + B) * (n + m) >= 2 * t * n
    go_down = jnp.where(must_down, True, jnp.where(must_right, False,
                                                   ~heur_right))
    lo_new = lo + go_down.astype(jnp.int32)

    # ---- 2. Align previous-diagonal neighbours to the new band ----
    # down: up[k] = prev[k],   left[k] = prev[k+1]
    # right: up[k] = prev[k-1], left[k] = prev[k]
    def pick_up(a, fill):
        return jnp.where(go_down, a, _shift_down(a, fill))

    def pick_left(a, fill):
        return jnp.where(go_down, _shift_up(a, fill), a)

    up_H = pick_up(s_H, NEG)
    up_x = pick_up(s_x, jnp.int32(0))
    up_v = pick_up(s_v, jnp.int32(0))
    left_H = pick_left(s_H, NEG)
    left_y = pick_left(s_y, jnp.int32(0))
    left_u = pick_left(s_u, jnp.int32(0))

    up_valid = up_H > DEAD_THRESHOLD
    left_valid = left_H > DEAD_THRESHOLD

    # ---- 3. Cell coordinates, masks, substitution scores ----
    k = jnp.arange(B, dtype=jnp.int32)
    i_vec = lo_new + k
    j_vec = t - i_vec
    valid = (i_vec >= 0) & (i_vec <= n) & (j_vec >= 0) & (j_vec <= m)
    interior = valid & (i_vec >= 1) & (j_vec >= 1)
    brow = valid & (i_vec == 0) & (j_vec >= 1)   # boundary row 0
    bcol = valid & (j_vec == 0) & (i_vec >= 1)   # boundary column 0

    qb = q_pad[jnp.clip(i_vec - 1, 0, q_pad.shape[0] - 1)]
    rb = r_pad[jnp.clip(j_vec - 1, 0, r_pad.shape[0] - 1)]
    is_match = (qb == rb) & (qb < 4) & (rb < 4)
    s = jnp.where(is_match, jnp.int32(sc.match),
                  jnp.int32(-sc.mismatch))

    # ---- 4. Parallelized shifted update (Eq. (4)) ----
    x_arm = jnp.where(up_valid, up_x, NEG)
    y_arm = jnp.where(left_valid, left_y, NEG)
    v_up = jnp.where(up_valid, up_v, oe)      # neutral: pretend dV_up = 0
    u_left = jnp.where(left_valid, left_u, oe)
    diag_valid = up_valid | left_valid
    s_arm = jnp.where(diag_valid, s + shift, NEG)

    a_new = jnp.maximum(jnp.maximum(s_arm, x_arm), y_arm)
    u_new = a_new - v_up
    v_new = a_new - u_left
    x_new = jnp.maximum(a_new, x_arm + o) - u_left
    y_new = jnp.maximum(a_new, y_arm + o) - v_up

    H_new = jnp.where(up_valid, up_H + u_new - oe,
                      jnp.where(left_valid, left_H + v_new - oe, NEG))

    # ---- 5. Traceback flags (paper Eq. (5), 4-bit) ----
    if collect_tb:
        direction = jnp.where(a_new == s_arm, 0,
                              jnp.where(a_new == x_arm, 1, 2))
        ext_e = (x_arm + o) > a_new
        ext_f = (y_arm + o) > a_new
        code = (direction + 4 * ext_e.astype(jnp.int32)
                + 8 * ext_f.astype(jnp.int32)).astype(jnp.uint8)
        code = jnp.where(interior, code, jnp.uint8(0))
        # Pack two lanes per byte inside the scan step: the (B,) flag
        # vector never leaves the step unpacked (DESIGN.md §5).
        code = pack_tb_lanes(code)
    else:
        code = None

    # ---- 6. Boundary overrides (constants derived in core.diff_dp) ----
    ob = jnp.int32(o)
    if mode == "semiglobal":
        # Free leading reference gap: H(0,j) = 0 for all j, so
        # dV(0,j) = 0 -> v' = o+e; dE(0,j) = -(o+e) -> x' = o+e.
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

    # Dead cells.
    H_new = jnp.where(valid, H_new, NEG)
    u_new = jnp.where(valid, u_new, 0)
    v_new = jnp.where(valid, v_new, 0)
    x_new = jnp.where(valid, x_new, 0)
    y_new = jnp.where(valid, y_new, 0)

    # ---- 7. X-drop retire rule + score capture ----
    done = t == (n + m)
    in_sweep = t <= (n + m)
    if xdrop is None:
        # Today's behaviour: only the ragged-length freeze applies.
        active = in_sweep
        pair_best = state.pair_best
        retired_at = state.retired_at
    else:
        # Retire when the whole live band fell > xdrop below the pair's
        # running best (dead cells are NEG, so the band max is over live
        # cells only). ~done keeps the final corner step eligible for
        # score capture: a pair never retires on its last diagonal.
        band_max = jnp.max(H_new)
        pb_new = jnp.maximum(state.pair_best, band_max)
        newly = in_sweep & (state.retired_at == 0) & ~done & \
            (band_max < pb_new - jnp.int32(xdrop))
        retired_at = jnp.where(newly, t, state.retired_at)
        active = in_sweep & (retired_at == 0)
        pair_best = jnp.where(active, pb_new, state.pair_best)

    k_corner = jnp.clip(n - lo_new, 0, B - 1)
    # Gate on active too: a retired pair's recomputed (frozen-carry)
    # planes must never leak into score capture. With xdrop=None this is
    # a no-op (done implies active), keeping one code path bit-exact.
    score = jnp.where(done & active, H_new[k_corner], state.score)
    final_lo = jnp.where(done & active, lo_new, state.final_lo)

    # Extension / local-max tracking (paper §III-A2: local traceback
    # starts from the max-score cell). Only interior cells compete —
    # in semiglobal mode only cells on the last read row (free trailing
    # reference gap: the alignment may end at any window column).
    elig = interior & active
    if mode == "semiglobal":
        elig = elig & (i_vec == n)
    H_masked = jnp.where(elig, H_new, NEG)
    k_best = jnp.argmax(H_masked)
    cand = H_masked[k_best]
    better = cand > state.best
    best = jnp.where(better, cand, state.best)
    best_i = jnp.where(better, i_vec[k_best], state.best_i)
    best_j = jnp.where(better, j_vec[k_best], state.best_j)

    # Freeze the carry once past the final diagonal (vmap with ragged
    # lengths runs extra steps for shorter pairs) — and, under xdrop,
    # once retired (same freeze, so surviving pairs are unaffected).
    def keep(new, old):
        return jnp.where(active, new, old)

    H_st, u_st, v_st, x_st, y_st, base_st = _narrow(
        H_new, u_new, v_new, x_new, y_new, cell_dtype)
    new_state = BandState(
        lo=keep(lo_new, state.lo), u=keep(u_st, state.u),
        v=keep(v_st, state.v), x=keep(x_st, state.x),
        y=keep(y_st, state.y), H=keep(H_st, state.H),
        base=keep(base_st, state.base),
        score=score, final_lo=final_lo,
        best=best, best_i=best_i, best_j=best_j,
        pair_best=pair_best, retired_at=retired_at)
    ys = (code, keep(lo_new, state.lo)) if collect_tb else keep(lo_new, state.lo)
    return new_state, ys


def _xdrop_sweep(step, state0: BandState, T: int, band: int,
                 collect_tb: bool, n, m):
    """Chunked wavefront sweep for the xdrop path: a `lax.while_loop`
    over `XDROP_CHUNK`-step scan chunks whose condition drops as soon as
    the pair is retired or past its true trip count, so the CPU oracle
    stops paying for the padded sweep exactly like the Pallas kernels'
    chunk skip. Under vmap the loop runs while ANY batch lane is live
    and per-lane selects keep finished lanes' carries frozen — savings
    are per lockstep batch, matching the kernels' per-tile flag.

    Returns (final state, tb[:T] or None, los[:T] or None).
    """
    chunk = min(XDROP_CHUNK, T)
    n_chunks = -(-T // chunk)
    T_pad = n_chunks * chunk

    def run_chunk(c, state):
        ts = c * chunk + jnp.arange(1, chunk + 1, dtype=jnp.int32)
        return jax.lax.scan(step, state, ts)

    def live(c, state):
        return (c < n_chunks) & (state.retired_at == 0) & \
            (c * chunk < n + m)

    if collect_tb:
        tb0 = jnp.zeros((T_pad, packed_tb_width(band)), jnp.uint8)
        lo0 = jnp.zeros((T_pad,), jnp.int32)

        def body(carry):
            c, state, tb_buf, lo_buf = carry
            state, (code, los) = run_chunk(c, state)
            tb_buf = jax.lax.dynamic_update_slice(tb_buf, code,
                                                  (c * chunk, 0))
            lo_buf = jax.lax.dynamic_update_slice(lo_buf, los, (c * chunk,))
            return c + 1, state, tb_buf, lo_buf

        _, state, tb_buf, lo_buf = jax.lax.while_loop(
            lambda carry: live(carry[0], carry[1]), body,
            (jnp.int32(0), state0, tb0, lo0))
        return state, tb_buf[:T], lo_buf[:T]

    def body(carry):
        c, state = carry
        state, _ = run_chunk(c, state)
        return c + 1, state

    _, state = jax.lax.while_loop(lambda carry: live(*carry), body,
                                  (jnp.int32(0), state0))
    return state, None, None


@functools.partial(jax.jit, static_argnames=("sc", "band", "adaptive",
                                             "collect_tb", "mode", "t_max",
                                             "cell_dtype", "xdrop"))
def banded_align(q_pad, r_pad, n, m, *, sc: ScoringConfig, band: int,
                 adaptive: bool = True, collect_tb: bool = True,
                 mode: str = "global", t_max: int | None = None,
                 cell_dtype: str = "int32", xdrop: int | None = None):
    """Align one (query, reference) pair with the adaptive banded
    parallelized DP.

    Args:
      q_pad: (n_pad,) int8/int32 encoded query (padded with 4).
      r_pad: (m_pad,) encoded reference.
      n, m: true lengths (traced scalars; enables ragged vmap batches).
      sc: scoring config (static).
      band: band width B (static).
      adaptive: adaptive wavefront direction on/off (Table V ablation).
      collect_tb: stream traceback flags (off = score-only, Fig. 14).
      t_max: static trimmed sweep length — the wavefront runs exactly
        t_max steps instead of the full padded n_pad + m_pad (§VI-F: the
        required trip count is the *true* n + m). Must satisfy
        t_max >= n + m for every pair in the (vmapped) batch; scores and
        CIGARs are invariant to any valid choice because the carry
        freezes past t = n + m. None = full padded sweep.
      cell_dtype: "int32" (default) or "narrow" — carry the wavefront
        state as int8 difference planes + int16 band-relative H (paper
        §IV bit-width reduction). Bit-exact with int32 whenever
        `validate_narrow_cells(sc, band)` accepts the config (callers
        should invoke the guard; it is not repeated per trace here).
      xdrop: X-drop early-exit threshold (static). A pair retires the
        first step its live-band max H falls more than xdrop below the
        pair's running best; retired pairs freeze their carry (the same
        freeze as t > n + m), report 'status' = the retiring step, keep
        'score' at the NEG sentinel, and — via a chunked
        `lax.while_loop` sweep — stop paying for the remaining trip
        count. None (default) = today's full sweep, bit-exact; any
        surviving pair is bit-identical either way.

    Returns a dict with 'score' (int32), 'status' (int32: 0 = aligned,
    k > 0 = retired by xdrop at step k), and when collect_tb: 'tb'
    ((T, ceil(B/2)) uint8 — 4-bit flags packed two lanes per byte, even
    lane in the low nibble; see `pack_tb_lanes`) and 'los' ((T+1,) int32
    band offsets, los[0]=0), where T = t_max or n_pad + m_pad.
    """
    q_pad = q_pad.astype(jnp.int32)
    r_pad = r_pad.astype(jnp.int32)
    T = int(t_max) if t_max is not None \
        else q_pad.shape[0] + r_pad.shape[0]
    n = jnp.asarray(n, jnp.int32)
    m = jnp.asarray(m, jnp.int32)

    step = functools.partial(_step, sc, band, adaptive, collect_tb, mode,
                             cell_dtype, xdrop, q_pad, r_pad, n, m)
    state0 = _init_state(band, mode, cell_dtype)
    if xdrop is None:
        state, ys = jax.lax.scan(step, state0,
                                 jnp.arange(1, T + 1, dtype=jnp.int32))
        code, los = ys if collect_tb else (None, None)
    else:
        state, code, los = _xdrop_sweep(step, state0, T, band, collect_tb,
                                        n, m)
    out = {"score": state.score, "final_lo": state.final_lo,
           "best_score": state.best, "best_i": state.best_i,
           "best_j": state.best_j, "status": state.retired_at}
    if collect_tb:
        out["tb"] = code
        out["los"] = jnp.concatenate([jnp.zeros((1,), jnp.int32), los])
    return out


def banded_align_batch(q_batch, r_batch, n_batch, m_batch, *, sc, band,
                       adaptive=True, collect_tb=True, mode="global",
                       t_max: int | None = None,
                       cell_dtype: str = "int32",
                       xdrop: int | None = None):
    """Sequence-level parallelism: vmap over a padded batch."""
    fn = functools.partial(banded_align, sc=sc, band=band,
                           adaptive=adaptive, collect_tb=collect_tb,
                           mode=mode, t_max=t_max, cell_dtype=cell_dtype,
                           xdrop=xdrop)
    return jax.vmap(fn)(q_batch, r_batch, n_batch, m_batch)


# ---------------------------------------------------------------------------
# Traceback decode (paper §V-C3) — host-side, mirroring the peripheral
# traceback logic (the ReRAM array never walks the path; dedicated logic
# does). Exact affine walk using the 4-bit flags.
# ---------------------------------------------------------------------------

def traceback_banded(tb: np.ndarray, los: np.ndarray, n: int, m: int,
                     band: int) -> list[tuple[str, int]]:
    """Decode one packed (T, ceil(B/2)) flag plane into a CIGAR.

    Lane k of step t (the cell (i, j) with i + j = t and k = i - los[t])
    lives in byte ``tb[t-1, k // 2]``: low nibble for even k, high nibble
    for odd k (`pack_tb_lanes` layout). Flags: bits 0-1 direction
    (0 diag / 1 E / 2 F), bit 2 E-extend, bit 3 F-extend (the extend bit
    of cell (i,j) describes the E/F value *entering* cell (i+1,j) /
    (i,j+1), per the Eq. (4) regrouping).

    Per-pair oracle — the production path is `traceback_banded_batch`.
    """
    tb = np.asarray(tb)
    los = np.asarray(los)

    def code(i, j):
        t = i + j
        k = i - int(los[t])
        if t < 1 or k < 0 or k >= band:
            return None  # path escaped the band: heuristic loss
        return int(select_tb_nibble(int(tb[t - 1, k >> 1]), k))

    ops: list[str] = []
    i, j = n, m
    state = "M"
    while i > 0 or j > 0:
        if i == 0:
            ops.append("D")
            j -= 1
            continue
        if j == 0:
            ops.append("I")
            i -= 1
            continue
        c = code(i, j)
        if c is None:
            # Escaped the band — fall back to a diagonal step (should not
            # happen for paths the band actually scored).
            ops.append("M")
            i -= 1
            j -= 1
            continue
        if state == "M":
            d = c & 3
            if d == 0:
                ops.append("M")
                i -= 1
                j -= 1
            elif d == 1:
                state = "E"
            else:
                state = "F"
        elif state == "E":
            ops.append("I")
            up = code(i - 1, j)
            ext = bool(up & 4) if (up is not None and i - 1 >= 1 and j >= 1) else False
            i -= 1
            if not ext:
                state = "M"
        else:  # "F"
            ops.append("D")
            left = code(i, j - 1)
            ext = bool(left & 8) if (left is not None and j - 1 >= 1 and i >= 1) else False
            j -= 1
            if not ext:
                state = "M"
    ops.reverse()
    cigar: list[tuple[str, int]] = []
    for op in ops:
        if cigar and cigar[-1][0] == op:
            cigar[-1] = (op, cigar[-1][1] + 1)
        else:
            cigar.append((op, 1))
    return cigar


# Batched traceback op codes (0 = no emission this sweep iteration).
_OP_CHARS = "?MID"
_OP_M, _OP_I, _OP_D = 1, 2, 3


def traceback_banded_batch(tb: np.ndarray, los: np.ndarray, n, m,
                           band: int, *, starts=None
                           ) -> list[list[tuple[str, int]]]:
    """Vectorised CIGAR decode of a whole dispatch group at once.

    Walks all N tracebacks in lockstep: every sweep iteration advances every
    still-active pair by one traceback step with O(N) numpy gathers instead
    of a per-pair Python loop. Semantics are identical to per-pair
    `traceback_banded` (same flag encoding, same band-escape fallback).

    Decodes straight from the *packed* plane: each flag lookup is one byte
    gather plus a shift/mask nibble select, so the unpacked (N, T, B)
    layout is never materialised on the host (the host fetch per dispatch
    group is the packed ceil(B/2)-byte rows the backend produced).

    Args:
      tb: (N, T, ceil(B/2)) uint8 packed flag planes (`pack_tb_lanes`
        layout: even lane in the low nibble, odd lane in the high nibble).
      los: (N, T+1) int32 band offsets.
      n, m: (N,) true lengths (the default traceback start cells).
      band: band width B shared by the group.
      starts: optional (N, 2) start cells (i, j) — pass the tracked best
        cells for semiglobal/extension mode; defaults to (n, m).

    Returns a list of N CIGARs ([(op, run_len), ...]).
    """
    tb = np.asarray(tb)
    los = np.asarray(los)
    n = np.asarray(n, np.int64).reshape(-1)
    m = np.asarray(m, np.int64).reshape(-1)
    N = tb.shape[0]
    if N == 0:
        return []
    T = tb.shape[1]
    if starts is None:
        i, j = n.copy(), m.copy()
    else:
        starts = np.asarray(starts, np.int64)
        i, j = starts[:, 0].copy(), starts[:, 1].copy()

    cap = max(int((i + j).max()), 1)
    ops_buf = np.zeros((N, cap), np.uint8)
    ops_len = np.zeros(N, np.int64)
    state = np.zeros(N, np.uint8)  # 0 = M, 1 = E (ins run), 2 = F (del run)
    idx = np.arange(N)

    def lookup(ii, jj):
        """Flags at (ii, jj) per pair + in-band validity (t >= 1 and the
        lane inside [0, band)). One byte gather from the packed plane,
        then a nibble select by lane parity."""
        t = ii + jj
        k = ii - los[idx, np.clip(t, 0, los.shape[1] - 1)]
        ok = (t >= 1) & (k >= 0) & (k < band)
        kc = np.clip(k, 0, band - 1)
        byte = tb[idx, np.clip(t - 1, 0, T - 1), kc >> 1]
        return select_tb_nibble(byte, kc), ok

    while True:
        active = (i > 0) | (j > 0)
        if not active.any():
            break
        c, in_band = lookup(i, j)

        emit = np.zeros(N, np.uint8)
        di = np.zeros(N, np.int64)
        dj = np.zeros(N, np.int64)
        new_state = state.copy()

        # Boundary row/column: forced gaps.
        b_del = active & (i == 0)
        emit[b_del] = _OP_D
        dj[b_del] = 1
        b_ins = active & (i > 0) & (j == 0)
        emit[b_ins] = _OP_I
        di[b_ins] = 1

        interior = active & (i > 0) & (j > 0)
        # Escaped the band: diagonal fallback (heuristic loss).
        esc = interior & ~in_band
        emit[esc] = _OP_M
        di[esc] = 1
        dj[esc] = 1

        core = interior & in_band
        d = c & 3
        in_m = core & (state == 0)
        m_diag = in_m & (d == 0)
        emit[m_diag] = _OP_M
        di[m_diag] = 1
        dj[m_diag] = 1
        # d != 0: enter a gap run — state change only, no emission/move.
        new_state[in_m & (d == 1)] = 1
        new_state[in_m & (d >= 2)] = 2

        in_e = core & (state == 1)
        emit[in_e] = _OP_I
        di[in_e] = 1
        cu, up_ok = lookup(i - 1, j)
        ext_e = up_ok & (i - 1 >= 1) & (j >= 1) & ((cu & 4) != 0)
        new_state[in_e & ~ext_e] = 0

        in_f = core & (state == 2)
        emit[in_f] = _OP_D
        dj[in_f] = 1
        cl, left_ok = lookup(i, j - 1)
        ext_f = left_ok & (j - 1 >= 1) & (i >= 1) & ((cl & 8) != 0)
        new_state[in_f & ~ext_f] = 0

        do = active & (emit != 0)
        ops_buf[idx[do], ops_len[do]] = emit[do]
        ops_len[do] += 1
        i -= np.where(active, di, 0)
        j -= np.where(active, dj, 0)
        state = np.where(active, new_state, state).astype(np.uint8)

    cigars: list[list[tuple[str, int]]] = []
    for p in range(N):
        ops = ops_buf[p, :ops_len[p]][::-1]
        if ops.size == 0:
            cigars.append([])
            continue
        bounds = np.flatnonzero(np.diff(ops)) + 1
        seg_starts = np.concatenate([[0], bounds])
        seg_ends = np.concatenate([bounds, [ops.size]])
        cigars.append([(_OP_CHARS[int(ops[s])], int(e - s))
                       for s, e in zip(seg_starts, seg_ends)])
    return cigars
