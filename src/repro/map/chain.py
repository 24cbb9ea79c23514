"""Anchor chaining — colinear seed selection between seeding and alignment.

Seeding (`repro.map.index`) returns anchors: (read position, reference
position) pairs where a k-length exact match exists. Chaining finds the
highest-scoring *colinear* subset — anchors that advance in both read
and reference — which localises the read to one candidate reference
window per chain; only those windows go to the banded aligner.

Scoring is minimap2-style (Li 2018, Eq. 1): extending a chain from
anchor j to anchor i (with dq = q_i - q_j > 0, dr = r_i - r_j > 0) gains
the new matched bases min(dq, dr, k) minus a concave gap cost on the
diagonal drift dd = |dr - dq|:

    cost(dd) = dd * k // 100  +  ilog2(dd + 1) // 2

— the integer-arithmetic rendering of minimap2's 0.01·k·dd + 0.5·log2 dd
(pure int32 ops, so chain scores are bit-identical across platforms and
backends, which the end-to-end mapper identity tests rely on). The DP

    f(i) = max( k,  max_{j: colinear, within gap limits} f(j) + gain(j,i) )

is a sequential recurrence over anchors sorted by reference position; it
runs as a jit'd `lax.fori_loop` batched over reads with `vmap` — an
O(A^2) score-and-backtrack whose inner maximisation is one vectorised
(A,) pass per anchor. The backtrack (predecessor walk from the best
endpoint) is fused into the same jit program. An O(A^2) numpy oracle in
tests/test_mapper.py pins the semantics.

Ragged anchor lists pad to a static `anchors_cap` (evenly-spaced
subsample when over — deterministic), and the batch dimension rounds up
to a multiple of 16 so the jit program count stays bounded.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

#: Sentinel for "no chain" / invalid anchor slots in the DP.
NEG = -(2 ** 30)

#: Batch-dimension pad multiple (bounds the number of compiled programs).
_BATCH_PAD = 16


@dataclasses.dataclass(frozen=True)
class ChainParams:
    """Static chaining configuration (part of the jit compile key).

    k: anchor length = per-anchor weight (the index's k).
    max_gap: longest read/reference advance a single chain join may
      bridge (minimap2 -g); joins past it are forbidden.
    max_diag_diff: largest diagonal drift |dr - dq| a join may have
      (minimap2's chaining bandwidth -r); bounds the indel budget.
    anchors_cap: static per-read anchor capacity A — longer lists are
      evenly subsampled, shorter ones padded.
    """

    k: int = 13
    max_gap: int = 5000
    max_diag_diff: int = 500
    anchors_cap: int = 128


@dataclasses.dataclass
class Chain:
    """One chained candidate: its score and member anchors (ascending
    reference order, genome coordinates)."""

    score: int
    q_pos: np.ndarray
    r_pos: np.ndarray

    @property
    def diag_start(self) -> int:
        """Chain-projected read start on the reference: the first
        anchor's diagonal r - q — the mapper's reported locus."""
        return int(self.r_pos[0] - self.q_pos[0])


def _ilog2(x):
    """floor(log2(x)) for positive int32 x, exactly: frexp's exponent
    is ceil(log2(x + 1)); int -> float32 is exact below 2^24 and
    max_diag_diff is far below that."""
    import jax.numpy as jnp

    return jnp.frexp(x.astype(jnp.float32))[1] - 1


def gap_cost(dd, k: int):
    """Integer minimap2-style concave gap cost on diagonal drift dd."""
    import jax.numpy as jnp

    lin = (dd * k) // 100
    log = jnp.where(dd > 0, _ilog2(dd + 1) // 2, 0)
    return lin + log


def _chain_one(qp, rp, valid, *, k: int, max_gap: int, max_dd: int):
    """Score + backtrack for one read's padded anchor list.

    Returns (f, pred, best_mask, best_idx): DP scores, predecessor
    indices (-1 = chain start), the membership mask of the best chain,
    and its endpoint index (-1 when no valid anchor exists).
    """
    import jax
    import jax.numpy as jnp

    A = qp.shape[0]
    neg = jnp.int32(NEG)
    kk = jnp.int32(k)

    def score_step(i, carry):
        f, pred = carry
        dq = qp[i] - qp
        dr = rp[i] - rp
        dd = jnp.abs(dr - dq)
        ok = ((dq > 0) & (dr > 0) & (dq <= max_gap) & (dr <= max_gap)
              & (dd <= max_dd) & valid)
        gain = jnp.minimum(jnp.minimum(dq, dr), kk) - gap_cost(dd, k)
        # Slots j >= i still hold NEG, so "j before i" needs no mask.
        cand = jnp.where(ok, f + gain, neg)
        j = jnp.argmax(cand)
        best = cand[j]
        extend = best > kk  # strict: ties start a fresh chain (leftmost)
        fi = jnp.where(valid[i],
                       jnp.where(extend, best, kk), neg)
        pi = jnp.where(valid[i] & extend, j.astype(jnp.int32),
                       jnp.int32(-1))
        return f.at[i].set(fi), pred.at[i].set(pi)

    f0 = jnp.full(A, neg, jnp.int32)
    pred0 = jnp.full(A, -1, jnp.int32)
    f, pred = jax.lax.fori_loop(0, A, score_step, (f0, pred0))

    best_idx = jnp.argmax(f)
    best_idx = jnp.where(f[best_idx] > neg, best_idx.astype(jnp.int32),
                         jnp.int32(-1))

    def walk_step(_, carry):
        cur, mask = carry
        safe = jnp.maximum(cur, 0)
        mask = mask.at[safe].set(mask[safe] | (cur >= 0))
        return jnp.where(cur >= 0, pred[safe], jnp.int32(-1)), mask

    _, best_mask = jax.lax.fori_loop(
        0, A, walk_step, (best_idx, jnp.zeros(A, bool)))
    return f, pred, best_mask, best_idx


@functools.lru_cache(maxsize=64)
def _chain_batch_fn(k: int, max_gap: int, max_dd: int):
    import jax

    one = functools.partial(_chain_one, k=k, max_gap=max_gap,
                            max_dd=max_dd)

    def chain_anchors(qp, rp, valid):
        return jax.vmap(one)(qp, rp, valid)

    return jax.jit(chain_anchors)


def _pad_anchors(anchor_sets, cap: int):
    """Stack ragged (q_pos, r_pos) anchor lists into padded (R', A)
    int32 arrays + valid mask (R' rounded up to the batch pad multiple;
    over-long lists evenly subsampled, deterministically)."""
    R = len(anchor_sets)
    Rp = max(-(-R // _BATCH_PAD) * _BATCH_PAD, _BATCH_PAD)
    qp = np.zeros((Rp, cap), np.int32)
    rp = np.zeros((Rp, cap), np.int32)
    valid = np.zeros((Rp, cap), bool)
    for i, (q, r) in enumerate(anchor_sets):
        a = len(q)
        if a > cap:
            take = np.linspace(0, a - 1, cap).round().astype(np.int64)
            q, r = np.asarray(q)[take], np.asarray(r)[take]
            a = cap
        qp[i, :a] = q
        rp[i, :a] = r
        valid[i, :a] = True
    return qp, rp, valid


def chain_batch(anchor_sets, params: ChainParams = ChainParams()):
    """Chain a batch of reads' anchor lists in one jit'd program.

    `anchor_sets` is a list of (q_pos, r_pos) pairs (one per read /
    strand probe; empty lists allowed). Returns per-set numpy
    (f, pred, best_mask, best_idx) tuples — `f[i]` is the best chain
    score ending at anchor i, `best_mask` the membership of the best
    chain (all False when the set was empty).
    """
    if not anchor_sets:
        return []
    cap = params.anchors_cap
    qp, rp, valid = _pad_anchors(anchor_sets, cap)
    fn = _chain_batch_fn(params.k, params.max_gap, params.max_diag_diff)
    f, pred, mask, best = (np.asarray(x) for x in fn(qp, rp, valid))
    return [(f[i], pred[i], mask[i], int(best[i]))
            for i in range(len(anchor_sets))]


def _extract(qp, rp, f, pred, idx) -> Chain:
    """Host-side predecessor walk from endpoint `idx` (for secondary
    chains; the best chain's walk is already fused in the jit)."""
    members = []
    cur = int(idx)
    while cur >= 0:
        members.append(cur)
        cur = int(pred[cur])
    members.reverse()
    return Chain(score=int(f[idx]),
                 q_pos=np.asarray([qp[i] for i in members], np.int64),
                 r_pos=np.asarray([rp[i] for i in members], np.int64))


def top_chains(q_pos, r_pos, chained, *, max_chains: int = 2,
               min_sep: int = 100, cap: int = 128):
    """The top `max_chains` non-overlapping chains of one anchor set.

    `chained` is one element of `chain_batch`'s output for this set.
    The best chain comes from the fused jit backtrack; secondaries are
    the best remaining DP endpoints whose reference span stays at least
    `min_sep` away from every already-taken chain (a chain through a
    suppressed region is discarded — it is the same candidate). Anchor
    arrays are the ORIGINAL (unpadded) lookup arrays; `cap` must match
    the ChainParams used, so endpoint indices line up.
    """
    f, pred, best_mask, best_idx = chained
    if best_idx < 0 or len(q_pos) == 0:
        return []
    qp, rp = np.asarray(q_pos, np.int64), np.asarray(r_pos, np.int64)
    if qp.size > cap:
        take = np.linspace(0, qp.size - 1, cap).round().astype(np.int64)
        qp, rp = qp[take], rp[take]
    a = qp.size
    out = [Chain(score=int(f[best_idx]), q_pos=qp[best_mask[:a]],
                 r_pos=rp[best_mask[:a]])]
    taken = [(int(out[0].r_pos[0]), int(out[0].r_pos[-1]))]
    scores = np.where(best_mask[:a], NEG, f[:a]).astype(np.int64)
    while len(out) < max_chains:
        for lo, hi in taken:
            near = (rp >= lo - min_sep) & (rp <= hi + min_sep)
            scores[near] = NEG
        idx = int(np.argmax(scores))
        if scores[idx] <= 0:
            break
        chain = _extract(qp, rp, f, pred, idx)
        span = (int(chain.r_pos[0]), int(chain.r_pos[-1]))
        scores[idx] = NEG
        # A secondary that walked back into a taken region is the same
        # candidate seen from a different endpoint — skip it.
        if any(span[0] <= hi + min_sep and span[1] >= lo - min_sep
               for lo, hi in taken):
            continue
        out.append(chain)
        taken.append(span)
    return out


__all__ = ["Chain", "ChainParams", "chain_batch", "top_chains",
           "gap_cost", "NEG"]
