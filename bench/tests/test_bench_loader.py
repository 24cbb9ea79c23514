"""A configuration, a traffic mix and a per-layer metric added as new
files only, and run through the harness on the CPU with no existing file
edited; and the entry point's refusal of a host without a TPU."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import loader  # noqa: E402

NEW_CONFIG = dict(json.loads((BENCH / "configs" / "coproc_1chip.json")
                             .read_text()),
                  name="tiny_pairs", genome_bp=50_000, warmup=False)
NEW_TRAFFIC = {"driver": "service_closed", "why": "test", "read_len": 60,
               "rates": {"sub": 0.03, "ins": 0.01, "del": 0.01},
               "outstanding_per_chip": 16, "pool_pairs": 4000,
               "warmup_passes": 2, "check_pairs": 8}
NEW_READER = '''
def read(run):
    return float(run.record["attempted"])
'''


@pytest.fixture()
def checkout(tmp_path, monkeypatch):
    """A benchmark tree holding every existing file, unedited, plus one
    new config, traffic mix, metric reader and BENCHMARK.json entries."""
    bench = tmp_path / "bench"
    for part in ("drivers", "metrics"):
        (bench / part).mkdir(parents=True)
        for f in (BENCH / part).glob("*.py"):
            shutil.copy(f, bench / part / f.name)
    shutil.copytree(BENCH / "configs", bench / "configs")
    shutil.copytree(BENCH / "traffic", bench / "traffic")
    (bench / "configs" / "tiny_pairs.json").write_text(json.dumps(NEW_CONFIG))
    (bench / "traffic" / "tiny_closed.json").write_text(
        json.dumps(NEW_TRAFFIC))
    (bench / "metrics" / "answers_seen.py").write_text(NEW_READER)
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_pairs", "source": "test",
                            "file": "bench/configs/tiny_pairs.json",
                            "reduced": []})
    spec["workloads"].append({"name": "tiny.closed", "config": "tiny_pairs",
                              "traffic": "tiny_closed", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "reads_per_s":
            m["workloads"].append("tiny.closed")
    spec["per_layer"].append({"name": "answers_seen", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "load generator",
                              "moves": "reads_per_s",
                              "workloads": ["tiny.closed"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(loader, "ROOT", tmp_path)
    monkeypatch.setattr(loader, "BENCH_DIR", bench)
    return tmp_path


def test_loader_finds_new_files_by_name(checkout):
    spec = loader.load_benchmark()
    cell = loader.find_cell(spec, "tiny.closed")
    assert loader.load_config(spec, cell["config"])["genome_bp"] == 50_000
    assert loader.load_traffic(cell["traffic"])["pool_pairs"] == 4000
    assert hasattr(loader.load_driver("service_closed"), "run")
    names = [m["name"] for m in loader.metrics_for(spec, "tiny.closed",
                                                   "per_layer")]
    assert names == ["answers_seen"]
    with pytest.raises(loader.BenchError):
        loader.load_traffic("no_such_mix")


@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_runs_through_the_harness(checkout, trace):
    import run
    out = run.measure(["--workload", "tiny.closed", "--seed", str(2**31 + 5),
                       "--seconds", "1", "--trace", str(trace)],
                      require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 16
    if trace:
        assert out["metrics"]["answers_seen"]["value"] == out["attempted"]
        assert "breakdown" in out
    else:
        assert set(out["metrics"]) == {"reads_per_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_entry_point_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "sr_map.closed",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=BENCH.parent, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "needs 1 TPU" in proc.stderr
