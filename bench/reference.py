"""Plain reference for the banded aligner: what each pair's answer must be.

An independent numpy rendering of the algorithm the program implements
(paper §IV-B), written from its published semantics in absolute Gotoh
terms: cell scores H, E (a gap that consumes read bases, CIGAR `I`) and
F (a gap that consumes reference bases, CIGAR `D`), with a gap of length
l costing o + l*e.

Band.  On anti-diagonal t the band holds the cells (i, j) with
i = lo_t + k for lanes k in [0, B) and j = t - i. Each step the band
moves right (lo stays) or down (lo + 1): down when it must to keep the
corner (n, m) reachable, right when lo has reached n, and otherwise
right exactly when lane 0's H exceeds lane B-1's on the previous
diagonal (the adaptive rule, paper §IV-B2).

Band edges.  After a move one edge lane misses a neighbour: after a
down move lane B-1 has no left cell, after a right move lane 0 has no
up cell. A missing neighbour contributes no gap arm. The diagonal term
of a cell is the score that its up neighbour holds for the cell to that
neighbour's left, or, when the up cell is missing, the score that the
left neighbour holds for the cell above it. Inside the band these are
the true H(i-1, j-1); at an edge they are what the neighbour itself saw
there. Every cell therefore carries `lv` (the score it takes its left
cell to have) and `uv` (the same for its up cell) beside H, E and F.
The gap arm that comes from the left is counted against the left cell's
`uv`; that is the same as Gotoh's F wherever the band holds both
neighbours of a cell.

Traceback flags per interior cell: the winning arm (diagonal first,
then E, then F on ties), and whether the E / F value leaving the cell
extends a gap (E + o > H, F + o > H: a tie opens a new gap). The walk
starts at (n, m) in global mode and at the best cell of the last read
row in semiglobal mode, and follows the flags back to (0, 0).

Output per pair, as the program's result dict names them: `score`
(H at the corner), `final_lo`, `best_score`, `best_i`, `best_j` and
`cigar` (a list of (op, run) tuples).
"""

from __future__ import annotations

import numpy as np

#: Dead-cell sentinel. Any live score is far above DEAD.
NEG = -(1 << 40)
DEAD = NEG // 2

_DIAG, _E, _F = 0, 1, 2
_EXT_E, _EXT_F = 4, 8


def align_band(reads, refs, *, band: int, mode: str, match: int,
               mismatch: int, gap_open: int, gap_extend: int,
               adaptive: bool = True) -> list[dict]:
    """Align each (read, ref) pair under the adaptive band of width
    `band`; returns one result dict per pair. Pairs run in lockstep,
    each frozen once its own n + m steps are done."""
    if mode not in ("global", "semiglobal"):
        raise ValueError(f"mode must be global or semiglobal, got {mode!r}")
    P, B = len(reads), int(band)
    if P == 0:
        return []
    o, e = gap_open, gap_extend
    oe = o + e
    n = np.array([len(r) for r in reads], np.int64)
    m = np.array([len(r) for r in refs], np.int64)
    q = np.full((P, int(n.max()) + 1), 4, np.int8)
    r = np.full((P, int(m.max()) + 1), 4, np.int8)
    for p in range(P):
        q[p, :n[p]] = reads[p]
        r[p, :m[p]] = refs[p]
    T = int((n + m).max())
    lane = np.arange(B, dtype=np.int64)[None, :]
    rows = np.arange(P)[:, None]

    # Diagonal t = 0: only (0, 0), H = 0, with the neutral neighbour
    # views that a zero difference state implies.
    H = np.full((P, B), NEG, np.int64)
    H[:, 0] = 0
    E = np.full((P, B), NEG, np.int64)
    F = np.full((P, B), NEG, np.int64)
    lv = np.zeros((P, B), np.int64)
    uv = np.zeros((P, B), np.int64)
    lv[:, 0] = uv[:, 0] = oe
    lo = np.zeros(P, np.int64)
    score = np.full(P, NEG, np.int64)
    final_lo = np.zeros(P, np.int64)
    best = np.full(P, NEG if mode == "semiglobal" else 0, np.int64)
    best_i = np.zeros(P, np.int64)
    best_j = np.zeros(P, np.int64)
    codes = np.zeros((P, T + 1, B), np.uint8)
    los = np.zeros((P, T + 1), np.int64)

    def shifted(a, down, fill):
        """Previous-diagonal lanes aligned to the new band: the up
        neighbour (i-1, j) and the left neighbour (i, j-1) of lane k."""
        prev_k1 = np.concatenate([np.full((P, 1), fill, a.dtype), a[:, :-1]],
                                 axis=1)      # lane k-1
        next_k1 = np.concatenate([a[:, 1:], np.full((P, 1), fill, a.dtype)],
                                 axis=1)      # lane k+1
        up = np.where(down[:, None], a, prev_k1)
        left = np.where(down[:, None], next_k1, a)
        return up, left

    for t in range(1, T + 1):
        active = t <= n + m
        must_down = lo + (n + m - t) < n - B + 1
        must_right = lo >= n
        if adaptive:
            go_right = H[:, 0] > H[:, B - 1]
        else:   # the fixed band: its centre follows the main diagonal
            go_right = (2 * lo + B) * (n + m) >= 2 * t * n
        down = np.where(must_down, True, np.where(must_right, False,
                                                   ~go_right))
        lo_new = lo + down
        i = lo_new[:, None] + lane
        j = t - i
        valid = (i >= 0) & (i <= n[:, None]) & (j >= 0) & (j <= m[:, None])
        interior = valid & (i >= 1) & (j >= 1)
        brow = valid & (i == 0) & (j >= 1)
        bcol = valid & (j == 0) & (i >= 1)

        H_up, H_left = shifted(H, down, NEG)
        E_up, _ = shifted(E, down, NEG)
        _, F_left = shifted(F, down, NEG)
        lv_up, _ = shifted(lv, down, 0)
        _, uv_left = shifted(uv, down, 0)
        up_ok = H_up > DEAD
        left_ok = H_left > DEAD
        any_ok = up_ok | left_ok

        qb = q[rows, np.clip(i - 1, 0, q.shape[1] - 1)]
        rb = r[rows, np.clip(j - 1, 0, r.shape[1] - 1)]
        s = np.where((qb == rb) & (qb < 4), match, -mismatch)
        D = np.where(up_ok, lv_up, uv_left)
        diag = np.where(any_ok, D + s, NEG)
        E_new = np.where(up_ok, np.maximum(H_up - oe, E_up - e), NEG)
        F_new = np.where(left_ok, np.maximum(H_left - oe, F_left - e)
                         + D - uv_left, NEG)
        H_new = np.where(any_ok, np.maximum(np.maximum(diag, E_new), F_new),
                         NEG)
        lv_new = np.where(left_ok, D + H_left - uv_left, D)
        uv_new = np.where(up_ok, D + H_up - lv_up, D)

        arm = np.where(H_new == diag, _DIAG, np.where(H_new == E_new, _E, _F))
        code = (arm + _EXT_E * (E_new + o > H_new)
                + _EXT_F * (F_new + o > H_new))
        codes[:, t] = np.where(interior, code, 0)

        # Boundary row 0 and column 0.
        row0 = np.zeros_like(j) if mode == "semiglobal" else -(o + j * e)
        prev_row0 = (np.zeros_like(j) if mode == "semiglobal"
                     else np.where(j == 1, 0, -(o + (j - 1) * e)))
        H_new = np.where(brow, row0, H_new)
        lv_new = np.where(brow, prev_row0, lv_new)
        uv_new = np.where(brow, row0 + e, uv_new)
        col0 = -(o + i * e)
        H_new = np.where(bcol, col0, H_new)
        uv_new = np.where(bcol, np.where(i == 1, 0, col0 + e), uv_new)
        lv_new = np.where(bcol, col0 + e, lv_new)
        border = brow | bcol
        E_new = np.where(border, NEG, E_new)
        F_new = np.where(border, NEG, F_new)
        H_new = np.where(valid, H_new, NEG)

        done = active & (t == n + m)
        corner = np.clip(n - lo_new, 0, B - 1)
        score = np.where(done, H_new[np.arange(P), corner], score)
        final_lo = np.where(done, lo_new, final_lo)
        elig = interior & active[:, None]
        if mode == "semiglobal":
            elig &= i == n[:, None]
        cand_all = np.where(elig, H_new, NEG)
        k_best = np.argmax(cand_all, axis=1)
        cand = cand_all[np.arange(P), k_best]
        better = cand > best
        best = np.where(better, cand, best)
        best_i = np.where(better, lo_new + k_best, best_i)
        best_j = np.where(better, t - lo_new - k_best, best_j)

        keep = active[:, None]
        H = np.where(keep, H_new, H)
        E = np.where(keep, E_new, E)
        F = np.where(keep, F_new, F)
        lv = np.where(keep, lv_new, lv)
        uv = np.where(keep, uv_new, uv)
        lo = np.where(active, lo_new, lo)
        los[:, t] = lo

    if mode == "semiglobal":
        start_i, start_j = best_i, best_j
    else:
        start_i, start_j = n, m
    cigars = _walk(codes, los, start_i, start_j, B)
    return [{"score": int(score[p]), "final_lo": int(final_lo[p]),
             "best_score": int(best[p]), "best_i": int(best_i[p]),
             "best_j": int(best_j[p]), "cigar": cigars[p]}
            for p in range(P)]


def _walk(codes, los, start_i, start_j, band) -> list[list[tuple]]:
    """Follow the flags from each start cell back to (0, 0), all pairs in
    lockstep. On row 0 the rest is deletions, on column 0 insertions; a
    cell outside the band is crossed diagonally."""
    P = codes.shape[0]
    i = np.asarray(start_i, np.int64).copy()
    j = np.asarray(start_j, np.int64).copy()
    state = np.zeros(P, np.int64)          # 0 match, 1 in E, 2 in F
    ops = [[] for _ in range(P)]
    idx = np.arange(P)

    def flags(ii, jj):
        t = ii + jj
        k = ii - los[idx, np.clip(t, 0, los.shape[1] - 1)]
        ok = (t >= 1) & (k >= 0) & (k < band)
        c = codes[idx, np.clip(t, 0, codes.shape[1] - 1),
                  np.clip(k, 0, band - 1)]
        return c.astype(np.int64), ok

    while True:
        live = (i > 0) | (j > 0)
        if not live.any():
            break
        c, ok = flags(i, j)
        op = np.zeros(P, np.int64)          # 0 none, 1 M, 2 I, 3 D
        di = np.zeros(P, np.int64)
        dj = np.zeros(P, np.int64)
        new_state = state.copy()
        on_row0 = live & (i == 0)
        on_col0 = live & (i > 0) & (j == 0)
        inner = live & (i > 0) & (j > 0)
        escaped = inner & ~ok
        core = inner & ok
        diag = core & (state == 0) & ((c & 3) == _DIAG)
        new_state[core & (state == 0) & ((c & 3) == _E)] = 1
        new_state[core & (state == 0) & ((c & 3) >= _F)] = 2
        in_e = core & (state == 1)
        in_f = core & (state == 2)
        cu, up_ok = flags(i - 1, j)
        ext_e = up_ok & (i - 1 >= 1) & (j >= 1) & ((cu & _EXT_E) != 0)
        cl, left_ok = flags(i, j - 1)
        ext_f = left_ok & (j - 1 >= 1) & (i >= 1) & ((cl & _EXT_F) != 0)
        new_state[in_e & ~ext_e] = 0
        new_state[in_f & ~ext_f] = 0
        for mask, code, a, b in ((on_row0, 3, 0, 1), (on_col0, 2, 1, 0),
                                 (escaped | diag, 1, 1, 1),
                                 (in_e, 2, 1, 0), (in_f, 3, 0, 1)):
            op[mask] = code
            di[mask] = a
            dj[mask] = b
        for p in np.flatnonzero(op):
            ops[p].append(int(op[p]))
        i -= di
        j -= dj
        state = np.where(live, new_state, state)

    out = []
    for seq in ops:
        cigar: list[tuple[str, int]] = []
        for code in reversed(seq):
            ch = "?MID"[code]
            if cigar and cigar[-1][0] == ch:
                cigar[-1] = (ch, cigar[-1][1] + 1)
            else:
                cigar.append((ch, 1))
        out.append(cigar)
    return out
