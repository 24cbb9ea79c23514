"""The comparison that decides `correct`: the plain reference agrees
with the program, the control and each fault of the timed path come out
not correct. Runs the whole harness on the CPU at a small size."""

import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import check  # noqa: E402
import loader  # noqa: E402
import simulate  # noqa: E402

SCORING = {"match": 2, "mismatch": 4, "gap_open": 4, "gap_extend": 2}
SMALL = {"genome_bp": 60_000, "pool_reads": 4000, "minibatch_bases": 48 * 150,
         "warmup_passes": 2, "pool_pairs": 400, "outstanding_per_chip": 12,
         "span_min": 200, "span_max": 700, "span_block": 12,
         "check_pairs": 400}


def program_answers(mode, profile, length, count, pad, seed):
    from repro.core.engine import AlignmentEngine
    rng = simulate.rng_for(seed, "t")
    g = simulate.random_genome(40_000, rng)
    block = simulate.sample_reads(g, [length] * count, profile, 0.0, rng)
    refs = [g[max(a - pad, 0):a + length + pad] for a in block.loci]
    out = AlignmentEngine(backend="reference").align(
        block.reads, refs, mode=mode, collect_tb=True)
    return [(rd, rf, {**{k: int(out[k][i]) for k in
                         check.FIELDS + ("status", "band")},
                      "cigar": out["cigars"][i]})
            for i, (rd, rf) in enumerate(zip(block.reads, refs))]


CASES = [("global", {"sub": 0.03, "ins": 0.01, "del": 0.01}, 150, 0),
         ("semiglobal", {"sub": 0.03, "ins": 0.01, "del": 0.01}, 150, 24),
         ("global", {"sub": 0.165, "ins": 0.05, "del": 0.085}, 400, 0)]


@pytest.mark.parametrize("mode,profile,length,pad", CASES)
def test_reference_agrees_with_the_program(mode, profile, length, pad):
    answers = program_answers(mode, profile, length, 24, pad, 1)
    assert check.mismatches(answers, mode=mode, scoring=SCORING) == (0, "")


@pytest.mark.parametrize("mode,profile,length,pad", CASES)
def test_control_with_a_fixed_band_is_not_correct(mode, profile, length, pad):
    answers = program_answers(mode, profile, length, 24, pad, 2)
    control = check.as_program(answers, mode=mode, scoring=SCORING)
    bad, first = check.mismatches(control, mode=mode, scoring=SCORING)
    assert bad > 0 and first


#: The long-read co-processor cell, added to the spec where
#: `BENCHMARK.json` does not hold it (its files are in `bench/`).
LR_CELL = {"name": "lr_coproc.closed", "config": "coproc_1chip",
           "traffic": "pacbio_pairs_closed", "chips": 1, "why": "test"}
LR_CONFIG = {"name": "coproc_1chip", "source": "test",
             "file": "bench/configs/coproc_1chip.json", "reduced": []}


@pytest.fixture()
def small(monkeypatch):
    load_config, load_traffic = loader.load_config, loader.load_traffic
    load_benchmark = loader.load_benchmark

    def benchmark(root=None):
        spec = load_benchmark(root)
        if all(w["name"] != LR_CELL["name"] for w in spec["workloads"]):
            spec["workloads"].append(LR_CELL)
            spec["configs"].append(LR_CONFIG)
        for m in spec["end_to_end"]:
            if m["name"] == "reads_per_s" and "workloads" in m:
                m["workloads"].append(LR_CELL["name"])
        return spec

    def config(spec, name, root=None):
        c = load_config(spec, name, root)
        c.update({k: v for k, v in SMALL.items() if k in c})
        return c

    def traffic(name, bench_dir=None):
        t = load_traffic(name, bench_dir)
        t.update({k: v for k, v in SMALL.items() if k in t})
        return t

    monkeypatch.setattr(loader, "load_benchmark", benchmark)
    monkeypatch.setattr(loader, "load_config", config)
    monkeypatch.setattr(loader, "load_traffic", traffic)


def measure(cell):
    import run
    return run.measure(["--workload", cell, "--seed", "123457",
                        "--seconds", "1", "--trace", "0"], require_tpu=False)


def break_finalize(monkeypatch, how):
    """Break the timed path where the answers are produced, from the
    window's start on (set-up and warm-up stay sound)."""
    import contextlib

    import harness
    from repro.core.engine import AlignmentEngine
    finalize = AlignmentEngine.finalize_group
    window = harness.Context.window
    timed = []

    @contextlib.contextmanager
    def flagged(self):
        with window(self):
            timed.append(True)
            yield self

    def broken(self, pending, **kw):
        out = finalize(self, pending, **kw)
        if not timed:
            return out
        n = len(out["score"])
        if how == "altered":
            out["score"] = np.asarray(out["score"]).copy()
            out["score"][0] += 2
        elif how == "half":
            # Half of the group left out: its rows answered by the rest.
            for key, val in out.items():
                half = list(val[:(n + 1) // 2]) * 2
                out[key] = (half[:n] if isinstance(val, list)
                            else np.asarray(half[:n]))
        elif how == "dropped":
            raise RuntimeError("group lost")
        return out

    monkeypatch.setattr(AlignmentEngine, "finalize_group", broken)
    monkeypatch.setattr(harness.Context, "window", flagged)


def test_sound_runs_are_correct(small):
    for cell in ("lr_coproc.closed", "sr_map.closed"):
        out = measure(cell)
        assert out["correct"], (cell, out["checks"])


@pytest.mark.parametrize("cell", ["lr_coproc.closed", "sr_map.closed"])
@pytest.mark.parametrize("how", ["altered", "half", "dropped"])
def test_faults_of_the_timed_path_are_not_correct(small, monkeypatch, how,
                                                  cell):
    break_finalize(monkeypatch, how)
    out = measure(cell)
    assert not out["correct"]
    failed = "unanswered" if how == "dropped" else "mismatched_pairs"
    assert out["checks"][failed]["value"] > 0


def test_misplaced_reads_fail_the_recall_floor(small, monkeypatch):
    from repro.map import ReadMapper
    map_batch = ReadMapper.map_batch

    def flipped(self, reads):
        results = map_batch(self, reads)
        for res in results:
            res.strand = 1 - res.strand
        return results

    monkeypatch.setattr(ReadMapper, "map_batch", flipped)
    out = measure("sr_map.closed")
    assert not out["correct"]
    assert out["checks"]["recall"]["value"] < 0.5


@pytest.mark.parametrize("name,rescue", [
    ("ecoli_illumina_map", "bulk"),
    ("ecoli_illumina_map_rescue_normal", "normal")])
def test_mapper_takes_the_configured_priorities(name, rescue):
    import json
    import types

    import program
    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    index = types.SimpleNamespace(k=int(config["k"]))
    service = types.SimpleNamespace(mode="semiglobal", collect_tb=True)
    mapper = program.make_mapper(index, service, config)
    assert mapper.priorities == ("normal", rescue)
