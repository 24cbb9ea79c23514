#!/usr/bin/env python3
"""Readings that set the limits of `correct`: the program's and the
control's, on several seeds.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed it runs the cell as `run.py` does (set-up, warm-up, a
window of `--seconds`), then compares the same seeded sample of the
window's answers twice with the plain reference: as the program gave
them, and with the control in the program's place (the reference with a
fixed band, `check.as_program`). It prints one JSON line per seed. Each
seed runs in a process of its own, as a benchmark run does (the parent
never touches JAX): a third `ReadMapper` window in one process once
stalled. The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys

import check
import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    seeds = args.seeds.split(",")
    if len(seeds) > 1:
        for seed in seeds:
            rc = subprocess.run([sys.executable, __file__, "--workload",
                                 args.workload, "--seconds", args.seconds,
                                 "--seeds", seed]).returncode
            if rc:
                return rc
        return 0
    seed = seeds[0]
    keep: dict = {}
    out = run.measure(["--workload", args.workload, "--seed", seed,
                       "--seconds", args.seconds, "--trace", "0"],
                      keep=keep)
    if out is None:
        return 2
    record, config, traffic = keep["record"], keep["config"], \
        keep["traffic"]
    picked = check.sample(record["answers"], int(traffic["check_pairs"]),
                          keep["ctx"].rng("check"))
    control = check.as_program(picked, mode=record["mode"],
                               scoring=config["scoring"])
    bad, first = check.mismatches(control, mode=record["mode"],
                                  scoring=config["scoring"])
    print(json.dumps({"seed": int(seed), "program": out["checks"],
                      "program_correct": out["correct"],
                      "control": {"mismatched_pairs": bad,
                                  "of": len(picked), "first": first},
                      "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
