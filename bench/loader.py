"""Finds a cell's parts by name: nothing here names a cell.

`BENCHMARK.json` at the repository root lists the cells. A cell names a
configuration (its file is given in `configs`), a traffic mix
(`bench/traffic/<name>.json`), and through the mix a driver
(`bench/drivers/<driver>.py`). Every metric of `BENCHMARK.json` has a
reader `bench/metrics/<metric name>.py`. A later cell, mix, driver or
metric is therefore new files and entries, never an edit of these.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class BenchError(RuntimeError):
    """A cell, file or metric that the benchmark cannot resolve."""


def load_benchmark(root: pathlib.Path | None = None) -> dict:
    root = ROOT if root is None else root
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def load_config(spec: dict, name: str,
                root: pathlib.Path | None = None) -> dict:
    root = ROOT if root is None else root
    for entry in spec["configs"]:
        if entry["name"] == name:
            return json.loads((root / entry["file"]).read_text())
    raise BenchError(f"no config {name!r} in BENCHMARK.json")


def load_traffic(name: str, bench_dir: pathlib.Path | None = None) -> dict:
    bench_dir = BENCH_DIR if bench_dir is None else bench_dir
    path = bench_dir / "traffic" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no traffic file {path}")
    return json.loads(path.read_text())


def _load_module(path: pathlib.Path, label: str):
    if not path.is_file():
        raise BenchError(f"no {label} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{label}_{path.stem.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_driver(name: str, bench_dir: pathlib.Path | None = None):
    """The driver module `bench/drivers/<name>.py` (it defines `run`)."""
    bench_dir = BENCH_DIR if bench_dir is None else bench_dir
    return _load_module(bench_dir / "drivers" / f"{name}.py", "driver")


def load_reader(name: str, bench_dir: pathlib.Path | None = None):
    """The metric reader `bench/metrics/<name>.py` (it defines `read`)."""
    bench_dir = BENCH_DIR if bench_dir is None else bench_dir
    return _load_module(bench_dir / "metrics" / f"{name}.py", "metric")


def metrics_for(spec: dict, cell: str, kind: str) -> list[dict]:
    """The `end_to_end` or `per_layer` metrics that a cell reports: those
    that list it, and those that list no cells at all."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]
