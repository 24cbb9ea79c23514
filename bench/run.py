#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the repository root. The cell's configuration, traffic mix and
driver are found by name (see `loader.py`). The run builds its inputs
from `--seed`, sets the system up and warms it, measures for `--seconds`,
checks a seeded sample of the window's answers against the plain
reference, and prints one JSON line as the last line of standard
output: `correct`, `attempted`, `failed`, `metrics` (the cell's
end-to-end metrics, or with `--trace 1` its per-layer ones, read from a
profiler trace of the window), `device`, with `--trace 1` `breakdown`,
and last `checks`: each number compared with its limit, also printed
as the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result line.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

import numpy as np  # noqa: E402

import loader  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(devices) -> dict:
    import jax
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def measure(argv, *, require_tpu: bool = True,
            keep: dict | None = None) -> dict | None:
    """One run of one cell; returns the result dict, or None when the
    host has no TPU or too few chips. `require_tpu=False` lets the tests
    drive a whole run on the CPU; `keep`, when given, receives the run's
    record and configuration (for `control.py`)."""
    args = parse(argv)
    spec = loader.load_benchmark()
    cell = loader.find_cell(spec, args.workload)
    config = loader.load_config(spec, cell["config"])
    traffic = loader.load_traffic(cell["traffic"])
    driver = loader.load_driver(traffic["driver"])

    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < int(cell["chips"])):
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              "device(s)", file=sys.stderr)
        return None
    import harness
    import program

    program.enable_cache()
    compiles = harness.CompileCounter().install()
    ctx = harness.Context(cell=cell, config=config, traffic=traffic,
                          seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace),
                          devices=devices[:int(cell["chips"])],
                          t_process=T_PROCESS, compiles=compiles)
    record = driver.run(ctx)
    device = device_info(ctx.devices)
    if keep is not None:
        keep.update(record=record, config=config, traffic=traffic, ctx=ctx)

    import check
    checks = check.checks(record, config, int(traffic["check_pairs"]),
                          ctx.rng("check"))
    answers = record["answers"]
    run = types.SimpleNamespace(
        record=record, setup_s=ctx.setup_s, window_s=ctx.window_s,
        compiles_in_window=ctx.compiles_in_window, trace=None,
        device_kind=device["kind"],
        work=(np.array([len(a[0]) for a in answers]),
              np.array([len(a[1]) for a in answers]),
              np.array([int(a[2]["band"]) for a in answers])))
    out = {"correct": all(c["ok"] for c in checks.values()),
           "attempted": record["attempted"], "failed": record["failed"]}
    if args.trace:
        import trace_reduce
        run.trace = trace_reduce.reduce(trace_reduce.load(ctx.trace_dir))
        ctx.drop_trace()
        kind = "per_layer"
        device["busy_s"] = (sum(d["busy_s"] for d in run.trace["devices"])
                            / max(len(run.trace["devices"]), 1))
        device["window_s"] = run.trace["window_s"]
    else:
        kind = "end_to_end"
    metrics = {}
    for m in loader.metrics_for(spec, cell["name"], kind):
        value = loader.load_reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if args.trace:
        out["breakdown"] = {"device_ops": trace_reduce.top_ops(run.trace),
                            "idle_gaps": [list(g) for g in run.trace["gaps"]]}
    ctx.log(f"window {ctx.window_s:.3f} s, set-up {ctx.setup_s:.3f} s, "
            f"{record['reads']} reads, {ctx.compiles_in_window} programs "
            f"built in the window ({ctx.cache_hits_in_window} of them "
            f"loaded from the cache), {compiles.count} in all "
            f"({compiles.hits} loaded, {compiles.seconds:.1f} s), "
            f"{record.get('warmup_passes')} warm-up passes")
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    for k, c in checks.items():
        rel = ">=" if k == "recall" else "<="
        extra = f" (of {c['of']} compared)" if "of" in c else ""
        print(f"check {k}: {c['value']} {rel} {c['limit']}: "
              f"{'ok' if c['ok'] else 'FAILED'}{extra}"
              + (f"; first: {c['first']}" if c.get("first") else ""),
              file=sys.stderr)
    return out


def main(argv=None) -> int:
    out = measure(argv)
    if out is None:
        return 2
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
