"""The comparison that decides `correct`.

A sample of the alignments that the window answered, drawn from the seed
and always holding the longest pair, is aligned again by the plain
reference (`reference.py`) at the band the program reported, and every
field of each answer must agree exactly: score, final band offset, best
score, best cell and CIGAR (an exact comparison, so its limit is 0). A
request that never got an answer counts against `unanswered` (limit 0).
A cell whose configuration states a recall floor is also held to it.

The control (`--control`, never in a benchmark run) puts the reference
with a fixed band in the program's place: the band that the
configuration states is adaptive (paper §IV-B2), so the control breaks
that guarantee and must come out not correct.
"""

from __future__ import annotations

import numpy as np

import reference

FIELDS = ("score", "final_lo", "best_score", "best_i", "best_j")


def sample(answers, count: int, rng) -> list:
    """`count` answers drawn from the seed, with the longest pair."""
    if not answers:
        return []
    sizes = np.array([len(rd) + len(rf) for rd, rf, _ in answers])
    pick = set(rng.choice(len(answers), size=min(count, len(answers)),
                          replace=False).tolist())
    pick.add(int(np.argmax(sizes)))
    return [answers[k] for k in sorted(pick)]


def mismatches(answers, *, mode: str, scoring: dict,
               adaptive: bool = True) -> tuple[int, str]:
    """How many answers differ from the reference, and the first one."""
    by_band: dict[int, list] = {}
    for rd, rf, res in answers:
        by_band.setdefault(int(res["band"]), []).append((rd, rf, res))
    bad, first = 0, ""
    for band, group in sorted(by_band.items()):
        want = reference.align_band([g[0] for g in group],
                                    [g[1] for g in group], band=band,
                                    mode=mode, adaptive=adaptive, **scoring)
        for (rd, rf, got), ref in zip(group, want):
            diff = [f for f in FIELDS if int(got[f]) != ref[f]]
            if int(got.get("status", 0)) != 0:
                diff.append("status")
            if [tuple(x) for x in got["cigar"] or []] != ref["cigar"]:
                diff.append("cigar")
            if diff:
                bad += 1
                if not first:
                    first = (f"n={len(rd)} m={len(rf)} band={band}: "
                             + ", ".join(f"{f} {got.get(f)!r:.40} vs "
                                         f"{ref.get(f)!r:.40}"
                                         for f in diff))
    return bad, first


def as_program(answers, *, mode: str, scoring: dict) -> list:
    """The control's answers: the reference with a fixed band, in the
    program's place."""
    by_band: dict[int, list] = {}
    for k, (rd, rf, res) in enumerate(answers):
        by_band.setdefault(int(res["band"]), []).append(k)
    out = list(answers)
    for band, ks in by_band.items():
        got = reference.align_band([answers[k][0] for k in ks],
                                   [answers[k][1] for k in ks], band=band,
                                   mode=mode, adaptive=False, **scoring)
        for k, res in zip(ks, got):
            out[k] = (answers[k][0], answers[k][1],
                      dict(res, band=band, status=0))
    return out


def checks(record: dict, config: dict, sample_size: int, rng) -> dict:
    """Each number compared, with its limit and whether it holds:
    {name: {"value", "limit", "ok"}}."""
    picked = sample(record["answers"], sample_size, rng)
    bad, first = mismatches(picked, mode=record["mode"],
                            scoring=config["scoring"])
    out = {
        "mismatched_pairs": {"value": bad, "limit": 0, "ok": bad == 0,
                             "of": len(picked), "first": first},
        "unanswered": {"value": record["failed"], "limit": 0,
                       "ok": record["failed"] == 0},
    }
    if "recall_floor" in config:
        recall = record["recall_hits"] / max(record["reads"], 1)
        out["recall"] = {"value": recall, "limit": config["recall_floor"],
                         "ok": recall >= config["recall_floor"]}
    return out
