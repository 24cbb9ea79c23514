"""Four-chip rehearsal: the `lr_coproc.mesh4` cell (the `coproc_4chip`
configuration under the `pacbio_pairs_closed` mix; added to the spec
here when `BENCHMARK.json` does not hold it) on four virtual CPU
devices, with the chip check skipped and sizes cut down in the test
only. Runs in a child process, since the device count is fixed when JAX
starts."""

import json
import os
import pathlib
import subprocess
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = r'''
import json, sys
sys.path.insert(0, sys.argv[1])
import loader, run
load_config, load_traffic = loader.load_config, loader.load_traffic
small = {"genome_bp": 60000, "pool_pairs": 600, "outstanding_per_chip": 8,
         "span_min": 200, "span_max": 600, "span_block": 8,
         "warmup_passes": 2, "check_pairs": 8}
def config(spec, name, root=None):
    c = load_config(spec, name, root)
    c.update({k: v for k, v in small.items() if k in c})
    return c
def traffic(name, bench_dir=None):
    t = load_traffic(name, bench_dir)
    t.update({k: v for k, v in small.items() if k in t})
    return t
loader.load_config, loader.load_traffic = config, traffic
load_benchmark = loader.load_benchmark
def benchmark(root=None):
    spec = load_benchmark(root)
    if not any(c["name"] == "coproc_4chip" for c in spec["configs"]):
        spec["configs"].append({"name": "coproc_4chip", "source": "test",
                                "file": "bench/configs/coproc_4chip.json",
                                "reduced": []})
    if not any(w["name"] == "lr_coproc.mesh4" for w in spec["workloads"]):
        spec["workloads"].append({"name": "lr_coproc.mesh4",
                                  "config": "coproc_4chip",
                                  "traffic": "pacbio_pairs_closed",
                                  "chips": 4, "why": "test"})
    return spec
loader.load_benchmark = benchmark
import harness
drive = loader.load_driver("service_closed").run
shards = []
def spy(ctx):
    record = drive(ctx)
    shards.append(record["shards"])
    return record
loader.load_driver = lambda name, bench_dir=None: type(
    "D", (), {"run": staticmethod(spy)})
out = run.measure(["--workload", "lr_coproc.mesh4", "--seed", "77",
                   "--seconds", "1", "--trace", "0"], require_tpu=False)
print(json.dumps({"out": out, "shards": shards[0]}))
'''


def test_mesh4_cell_runs_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(BENCH)],
                          cwd=BENCH.parent, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["shards"] == 4
    out = got["out"]
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert out["attempted"] > 32 and out["failed"] == 0
